"""Cross-query caching (trino_tpu.cache): the HBM-resident device
tier + the semantic result cache.

The oracle contract: a cached answer must be byte-identical to a cold
run of the same statement on every execution tier (local, mesh,
fleet), staleness must resolve through the generation counter (DML
through ANY executor invalidates), and cache residency must be the
lowest-priority memory in the pool — an over-cap query reservation
evicts cache entries via the revoker protocol instead of raising
ExceededMemoryLimitError. A warmed device-cache repeat pays zero
connector reads and zero new XLA compiles.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from trino_tpu import cache, memory, telemetry
from trino_tpu import types as T
from trino_tpu.connectors.base import TableSchema
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.exec import scan_cache
from trino_tpu.metadata import Metadata, Session

#: fleet 18940+, chaos 18960+, bench 18970+, storage 19010+,
#: elastic 19360+ — cache tests bind 19410+
BASE_PORT = 19410


@pytest.fixture(autouse=True)
def _fresh_device_tier():
    # DEVICE is process-global (content-addressed keys make sharing
    # safe) — but tests assert exact hit/miss traffic, so isolate
    cache.DEVICE.clear()
    yield
    cache.DEVICE.clear()


def _mem_runner():
    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    r = QueryRunner(md, Session(catalog="memory", schema="default"))
    r.execute("create table t (id bigint, v bigint)")
    r.execute("insert into t values (1, 10), (2, 20), (3, 30)")
    return r


def _enable(runner, result=True, device=False):
    runner.session.properties["result_cache_enabled"] = result
    runner.session.properties["device_cache_enabled"] = device


# ---- connector fingerprints ------------------------------------------------


def test_instance_idents_are_distinct_and_stable():
    a, b = MemoryConnector(), MemoryConnector()
    ia, _ = cache.connector_fingerprint(a)
    ib, _ = cache.connector_fingerprint(b)
    assert ia != ib
    assert cache.connector_fingerprint(a)[0] == ia  # stable per instance
    assert ia.startswith("id:")


def test_parquet_fingerprint_shared_across_instances(tmp_path):
    pytest.importorskip("pyarrow")
    from trino_tpu.connectors.parquet import (
        ParquetConnector, write_parquet_table,
    )

    root = str(tmp_path)
    write_parquet_table(
        root, "default", "t",
        TableSchema("t", [("k", T.BIGINT)]),
        {"k": np.arange(10, dtype=np.int64)},
    )
    a, b = ParquetConnector(root), ParquetConnector(root)
    ia, ca = cache.connector_fingerprint(a)
    ib, cb = cache.connector_fingerprint(b)
    # same files -> same ident AND same content digest
    assert (ia, ca) == (ib, cb)
    assert not ia.startswith("id:")
    # rewriting the data flips the content digest, not the ident
    time.sleep(0.01)  # mtime_ns granularity
    write_parquet_table(
        root, "default", "t",
        TableSchema("t", [("k", T.BIGINT)]),
        {"k": np.arange(20, dtype=np.int64)},
    )
    ia2, ca2 = cache.connector_fingerprint(a)
    assert ia2 == ia and ca2 != ca


def test_scan_cache_shared_across_connector_instances(tmp_path):
    # regression (satellite 1): the scan-page cache used to key by
    # connector INSTANCE, so two connectors over the same files each
    # paid their own host->device transfer and a rewrite through one
    # never invalidated the other's pages
    pytest.importorskip("pyarrow")
    from trino_tpu.connectors.parquet import (
        ParquetConnector, write_parquet_table,
    )

    root = str(tmp_path)
    write_parquet_table(
        root, "default", "pts",
        TableSchema("pts", [("k", T.BIGINT), ("v", T.BIGINT)]),
        {"k": np.arange(50, dtype=np.int64),
         "v": np.arange(50, dtype=np.int64) * 2},
    )

    def runner():
        md = Metadata()
        md.register_catalog("hive", ParquetConnector(root))
        return QueryRunner(md, Session(catalog="hive", schema="default"))

    r1 = runner()
    assert r1.execute("select sum(v) from pts").rows == [(2450,)]
    conn2 = ParquetConnector(root)  # fresh instance, same files
    assert scan_cache.SHARED.resident_tables(conn2) == [
        ("default", "pts")
    ], "second instance over the same files must see the warm pages"
    # an out-of-band rewrite busts the shared entry at the next probe
    time.sleep(0.01)
    write_parquet_table(
        root, "default", "pts",
        TableSchema("pts", [("k", T.BIGINT), ("v", T.BIGINT)]),
        {"k": np.arange(10, dtype=np.int64),
         "v": np.full(10, 7, dtype=np.int64)},
    )
    assert scan_cache.SHARED.resident_tables(conn2) == []
    r2 = runner()
    assert r2.execute("select sum(v) from pts").rows == [(70,)]


# ---- semantic result cache: local tier -------------------------------------


def test_result_cache_disabled_by_default():
    r = _mem_runner()
    r.execute("select sum(v) from t")
    res = r.execute("select sum(v) from t")
    assert res.cache_stats is None
    assert len(r.result_cache) == 0


def test_result_cache_hit_is_byte_identical_to_cold_run():
    warm = _mem_runner()
    cold = _mem_runner()
    _enable(warm)
    sql = "select id, v * 2 from t where v >= 20 order by id"
    first = warm.execute(sql)
    assert first.cache_stats["result"]["hit"] is False
    hit = warm.execute(sql)
    assert hit.cache_stats["result"]["hit"] is True
    ref = cold.execute(sql)
    assert hit.rows == first.rows == ref.rows
    assert hit.names == ref.names
    assert hit.ordered == ref.ordered
    # identical python values, byte for byte
    assert repr(hit.rows) == repr(ref.rows)


def test_result_cache_scoped_per_runner():
    # two runners never observe each other's entries (fault-injection
    # twins and A/B benches depend on this isolation)
    a, b = _mem_runner(), _mem_runner()
    _enable(a)
    _enable(b)
    sql = "select sum(v) from t"
    a.execute(sql)
    a.execute(sql)
    res = b.execute(sql)
    assert res.cache_stats["result"]["hit"] is False


def test_dml_invalidates_via_generation_counter():
    r = _mem_runner()
    _enable(r)
    sql = "select sum(v) from t"
    assert r.execute(sql).rows == [(60,)]
    assert r.execute(sql).cache_stats["result"]["hit"] is True
    r.execute("insert into t values (4, 40)")
    stale = r.execute(sql)
    assert stale.cache_stats["result"]["hit"] is False, (
        "post-DML probe must miss: the write bumped the generation"
    )
    assert stale.rows == [(100,)]
    # and the refreshed entry serves again
    assert r.execute(sql).rows == [(100,)]


def test_delete_and_update_invalidate_too():
    r = _mem_runner()
    _enable(r)
    sql = "select count(*), coalesce(sum(v), 0) from t"
    r.execute(sql)
    r.execute("delete from t where id = 1")
    res = r.execute(sql)
    assert res.cache_stats["result"]["hit"] is False
    assert res.rows == [(2, 50)]
    r.execute(sql)
    r.execute("update t set v = 100 where id = 2")
    res = r.execute(sql)
    assert res.cache_stats["result"]["hit"] is False
    assert res.rows == [(2, 130)]


def test_result_cache_lru_eviction_bounded():
    c = cache.SemanticResultCache(max_bytes=2048)
    tok = (("id:1", "default", "t", 0, 0),)
    for i in range(64):
        c.put(f"d{i}", ["a"], [(i,)] * 8, False, tok)
    assert c.resident_bytes <= 2048
    assert c.evictions > 0
    assert c.get("d0", tok) is None  # LRU-first
    assert c.get("d63", tok) is not None


def test_session_property_changes_segment_the_cache():
    # the digest folds in session properties: flipping one re-plans
    # under a different key instead of serving a stale answer
    r = _mem_runner()
    _enable(r)
    sql = "select sum(v) from t"
    r.execute(sql)
    r.session.properties["join_distribution_type"] = "PARTITIONED"
    assert r.execute(sql).cache_stats["result"]["hit"] is False


def test_explain_analyze_never_served_from_result_cache():
    r = _mem_runner()
    _enable(r)
    sql = "select sum(v) from t"
    r.execute(sql)
    r.execute(sql)
    text = "\n".join(
        row[0] for row in r.execute(f"explain analyze {sql}").rows
    )
    # EXPLAIN ANALYZE executes for real (its point is the live stats)
    assert "rows" in text.lower()


# ---- device tier -----------------------------------------------------------


def test_join_build_fragment_cached_in_device_tier():
    r = _mem_runner()
    r.execute("create table d (id bigint, name varchar)")
    r.execute("insert into d values (1, 'a'), (2, 'b'), (3, 'c')")
    _enable(r, result=False, device=True)  # isolate the device tier
    sql = (
        "select d.name, sum(t.v) from t, d where t.id = d.id "
        "group by d.name order by 1"
    )
    first = r.execute(sql)
    assert first.rows == [("a", 10), ("b", 20), ("c", 30)]
    assert len(cache.DEVICE) >= 1, "build side must be pinned"
    again = r.execute(sql)
    assert again.rows == first.rows
    assert again.cache_stats["device"]["hits"] >= 1
    # staleness: DML on the build side drops the fragment
    r.execute("insert into d values (4, 'z')")
    r.execute("insert into t values (4, 40)")
    post = r.execute(sql)
    assert post.rows == [("a", 10), ("b", 20), ("c", 30), ("z", 40)]


def test_warm_device_repeat_zero_scans_zero_compiles(tmp_path):
    # the headline serving property: a warmed repeat touches neither
    # the connector (zero host->device transfers) nor the compiler
    pytest.importorskip("pyarrow")
    from trino_tpu.connectors.parquet import (
        ParquetConnector, write_parquet_table,
    )

    root = str(tmp_path)
    write_parquet_table(
        root, "default", "f",
        TableSchema("f", [("k", T.BIGINT), ("v", T.BIGINT)]),
        {"k": np.arange(1000, dtype=np.int64),
         "v": np.arange(1000, dtype=np.int64)},
        row_group_size=100,
    )
    md = Metadata()
    conn = ParquetConnector(root)
    md.register_catalog("hive", conn)
    r = QueryRunner(md, Session(catalog="hive", schema="default"))
    _enable(r, result=False, device=True)
    # pushed domain -> _scan_pruned -> device-tier keyed on the filter
    sql = "select sum(v) from f where k < 500"
    first = r.execute(sql)
    assert first.rows == [(sum(range(500)),)]
    assert first.cache_stats["device"]["misses"] >= 1

    real_scan = conn.scan

    def poisoned(*a, **kw):
        raise AssertionError("warm repeat must not touch the connector")

    conn.scan = poisoned
    try:
        compiles = telemetry.XLA_COMPILES.value()
        warm = r.execute(sql)
    finally:
        conn.scan = real_scan
    assert warm.rows == first.rows
    assert warm.cache_stats["device"]["hits"] >= 1
    assert warm.cache_stats["device"]["misses"] == 0
    assert telemetry.XLA_COMPILES.value() == compiles, (
        "warmed repeat must compile nothing new"
    )


def test_device_tier_segments_by_pushed_domain(tmp_path):
    # a pruned row set is filter-specific: different pushed domains
    # must never share an entry (wrong-rows class, not a perf bug)
    pytest.importorskip("pyarrow")
    from trino_tpu.connectors.parquet import (
        ParquetConnector, write_parquet_table,
    )

    root = str(tmp_path)
    write_parquet_table(
        root, "default", "g",
        TableSchema("g", [("k", T.BIGINT)]),
        {"k": np.arange(100, dtype=np.int64)},
        row_group_size=10,
    )
    md = Metadata()
    md.register_catalog("hive", ParquetConnector(root))
    r = QueryRunner(md, Session(catalog="hive", schema="default"))
    _enable(r, result=False, device=True)
    assert r.execute("select count(*) from g where k < 30").rows == [(30,)]
    assert r.execute("select count(*) from g where k < 70").rows == [(70,)]
    assert r.execute("select count(*) from g where k < 30").rows == [(30,)]


# ---- memory governance: cache is the lowest-priority memory ---------------


def test_pool_revoker_evicts_cache_instead_of_raising():
    from trino_tpu.page import Page
    import jax.numpy as jnp

    pool = memory.MemoryPool(limit_provider=lambda: 100_000, node_id="n1")
    dev = cache.DeviceTableCache(max_bytes=1 << 30)
    mask = jnp.asarray(np.ones(4096, dtype=np.bool_))
    col_data = jnp.zeros(4096, dtype=jnp.int64)
    from trino_tpu.page import Column

    page = Page(
        ["x"], [Column(T.BIGINT, col_data)], mask,
        known_rows=4096, packed=True,
    )
    tok = (("id:test", "s", "t", cache.GENERATIONS.get("id:test", "s", "t"), 0),)
    assert dev.put(("scan", "id:test"), page, tok, pool=pool)
    resident = dev.resident_bytes
    assert resident > 0
    snap = pool.snapshot()["queries"]["cache"]
    assert snap["reserved_bytes"] == resident
    # a query reservation that only fits if the cache yields
    ctx = pool.query_context("q-over-cap")
    ctx.reserve(100_000 - resident + 1)  # would breach by 1 byte
    assert len(dev) == 0, "revoker must shed the entry"
    assert dev.evictions == 1
    cache_snap = pool.snapshot()["queries"].get("cache")
    assert cache_snap is None or cache_snap["reserved_bytes"] == 0
    ctx.free(100_000 - resident + 1)


def test_query_succeeds_when_cache_residency_would_exceed_cap():
    # end-to-end: warm the device tier, cap the pool BELOW resident
    # cache + query need, and the query must still succeed (entry
    # dropped) rather than die with ExceededMemoryLimitError
    r = _mem_runner()
    r.execute("create table d (id bigint, name varchar)")
    r.execute("insert into d values (1, 'a'), (2, 'b')")
    _enable(r, result=False, device=True)
    sql = (
        "select d.name, sum(t.v) from t, d where t.id = d.id "
        "group by d.name order by 1"
    )
    assert r.execute(sql).rows == [("a", 10), ("b", 20)]
    assert len(cache.DEVICE) >= 1
    resident = cache.DEVICE.resident_bytes
    peak = r.executor.memory_pool.peak_bytes
    cap = peak + resident // 2  # roomy for the query, not for both
    r.session.properties["query_max_memory_per_node"] = str(cap)
    res = r.execute(
        "select sum(t.v), count(d.name) from t, d where t.id = d.id"
    )
    assert res.rows == [(30, 2)]
    assert cache.DEVICE.evictions >= 1 or cache.DEVICE.resident_bytes == 0


def test_cluster_manager_never_picks_cache_context_as_victim():
    mgr = memory.ClusterMemoryManager()
    mgr.observe("n1", {"queries": {
        "cache": {"peak_bytes": 10_000_000},
        "q1": {"peak_bytes": 2_000},
    }})
    picked = mgr.pick_victim(1_000)
    assert picked is not None and picked[0] == "q1", (
        "the revocable cache context must never be the kill victim"
    )
    mgr2 = memory.ClusterMemoryManager()
    mgr2.observe("n1", {"queries": {
        "cache": {"peak_bytes": 10_000_000},
    }})
    assert mgr2.pick_victim(1_000) is None


# ---- observability ---------------------------------------------------------


def test_system_runtime_caches_table():
    from trino_tpu.connectors.system import SystemConnector

    r = _mem_runner()
    r.metadata.register_catalog("system", SystemConnector(runner=r))
    _enable(r)
    sql = "select sum(v) from t"
    r.execute(sql)
    r.execute(sql)
    rows = r.execute(
        "select tier, entries, hits, misses from system.runtime.caches "
        "order by tier"
    ).rows
    tiers = [row[0] for row in rows]
    assert tiers == ["device", "result", "scan_pages", "split_batches"]
    result_row = dict(zip(tiers, rows))["result"]
    assert result_row[1] >= 1 and result_row[2] >= 1


def test_explain_analyze_renders_cache_line():
    r = _mem_runner()
    r.execute("create table d (id bigint, name varchar)")
    r.execute("insert into d values (1, 'a')")
    _enable(r, result=False, device=True)
    sql = "select t.v from t, d where t.id = d.id"
    r.execute(sql)  # warm the fragment
    text = "\n".join(
        row[0] for row in r.execute(f"explain analyze {sql}").rows
    )
    assert "Cache:" in text


def test_result_cache_metrics_flow():
    before_h = telemetry.RESULT_CACHE_HITS.value()
    before_m = telemetry.RESULT_CACHE_MISSES.value()
    r = _mem_runner()
    _enable(r)
    sql = "select sum(v) from t"
    r.execute(sql)
    r.execute(sql)
    assert telemetry.RESULT_CACHE_HITS.value() == before_h + 1
    assert telemetry.RESULT_CACHE_MISSES.value() == before_m + 1


# ---- mesh tier -------------------------------------------------------------


def test_mesh_cached_results_byte_identical():
    from trino_tpu.parallel.core import make_mesh

    warm = QueryRunner.tpch("tiny", mesh=make_mesh(8))
    cold = QueryRunner.tpch("tiny")
    _enable(warm)
    sql = (
        "select l_returnflag, sum(l_quantity) from lineitem "
        "group by l_returnflag order by 1"
    )
    first = warm.execute(sql)
    hit = warm.execute(sql)
    assert hit.cache_stats["result"]["hit"] is True
    assert hit.rows == first.rows == cold.execute(sql).rows


# ---- fleet tier ------------------------------------------------------------


def _spawn_worker(port: int) -> subprocess.Popen:
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "trino_tpu.server.worker",
         "--port", str(port)],
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


@pytest.fixture()
def fleet(workers, tmp_path):
    from trino_tpu.server.fleet import FleetRunner

    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    return FleetRunner(
        workers, md, Session(catalog="tpch", schema="tiny"),
        spool_root=str(tmp_path), n_partitions=4,
    )


def test_fleet_cached_results_byte_identical(fleet):
    _enable(fleet._planner)  # fleet shares the planner's session
    sql = (
        "select l_returnflag, l_linestatus, sum(l_quantity) q "
        "from lineitem group by 1, 2 order by 1, 2"
    )
    first = fleet.execute(sql)
    assert first.cache_stats["result"]["hit"] is False
    hit = fleet.execute(sql)
    assert hit.cache_stats["result"]["hit"] is True
    assert hit.rows == first.rows
    assert hit.names == first.names
    assert hit.ordered == first.ordered
    cold = QueryRunner.tpch("tiny").execute(sql)
    assert hit.rows == cold.rows


def test_fleet_cache_hit_dispatches_no_tasks(fleet, monkeypatch):
    _enable(fleet._planner)
    sql = "select count(*) from orders"
    first = fleet.execute(sql)

    def no_dispatch(*a, **kw):  # a hit must short-circuit before here
        raise AssertionError("cache hit must not dispatch tasks")

    monkeypatch.setattr(fleet, "_execute_attempt", no_dispatch)
    hit = fleet.execute(sql)
    assert hit.rows == first.rows
    assert hit.cache_stats["result"]["hit"] is True


def test_serving_layer_shares_result_cache_across_queries(workers, tmp_path):
    # Each ServingRunner.execute builds a fresh per-query FleetRunner;
    # repeats only hit if they all probe the ONE shared cache.  This is
    # exactly the path an `or`-based fallback breaks when the shared
    # cache starts out empty (empty SemanticResultCache is falsy).
    from trino_tpu.testing import chaos as chaos_mod

    s = chaos_mod.make_serving(workers, str(tmp_path))
    sql = (
        "select sum(l_extendedprice * l_discount) from lineitem "
        "where l_discount between 0.05 and 0.07 and l_quantity < 24"
    )
    first = s.execute(sql)
    assert first.cache_stats["result"]["hit"] is False
    hit = s.execute(sql)
    assert hit.cache_stats["result"]["hit"] is True
    assert hit.rows == first.rows
    snap = s.result_cache.snapshot()
    assert snap["entries"] == 1
    assert snap["hits"] >= 1


def test_serving_cached_rows_equal_uncached_twin(workers, tmp_path):
    """The cache CI lane's round: one repeat-heavy schedule over three
    TPC-H templates through the serving layer with both tiers on, every
    answer (hit or miss) held to the rows of a twin with both tiers
    off — exactly, no tolerance — and the repeats really were hits."""
    import random

    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.testing import chaos as chaos_mod
    from trino_tpu.testing.golden import assert_rows_match

    mix = [QUERIES["q01"], QUERIES["q03"], QUERIES["q06"]]
    # zipf-ish weights 1/rank, fixed seed; every template at least once
    schedule = random.Random(11).choices(
        range(len(mix)), weights=[1.0, 0.5, 1 / 3], k=12
    )
    schedule[:len(mix)] = range(len(mix))

    def serving(cache_on: bool):
        s = chaos_mod.make_serving(workers, str(tmp_path))
        s.session.properties["result_cache_enabled"] = cache_on
        s.session.properties["device_cache_enabled"] = cache_on
        return s

    def hit(res) -> bool:
        return bool(((res.cache_stats or {}).get("result") or {}).get("hit"))

    twin = serving(False)
    try:
        uncached = [twin.execute(sql) for sql in mix]
    finally:
        twin.stop()
    assert not any(hit(r) for r in uncached)

    hits0 = telemetry.RESULT_CACHE_HITS.value()
    hits = []
    cached = serving(True)
    try:
        for idx in schedule:
            res = cached.execute(mix[idx])
            assert_rows_match(
                res.rows, uncached[idx].rows,
                ordered=uncached[idx].ordered, abs_tol=0.0,
            )
            hits.append(hit(res))
    finally:
        cached.stop()
    # first sight of a template misses, every repeat hits
    assert hits == [False] * len(mix) + [True] * (len(schedule) - len(mix))
    assert telemetry.RESULT_CACHE_HITS.value() - hits0 == sum(hits)
