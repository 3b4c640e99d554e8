"""Out-of-core streamed storage scans vs the sqlite oracle.

The SF100-opening storage subsystem end to end: row-group-granular
parquet splits with footer min/max + Hive partition pruning
(connectors/parquet), the memory-governed streamed scan operator
(exec/stream_scan), split-batch caching (exec/scan_cache), split-read
chaos retry (fault site ``scan-read``), and the fleet tier — one split
per task, coordinator-level dynamic filtering narrowing the probe
scan's domains before its row groups are read.

Every result is checked row-for-row against sqlite over the same data.
The whole module skips cleanly when pyarrow is absent (CI's default
matrix does not install it; the storage-smoke job does).
"""

import json
import os
import sqlite3
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

pytest.importorskip("pyarrow")

from trino_tpu import fault, telemetry
from trino_tpu import types as T
from trino_tpu.connectors.base import ColumnDomain, TableSchema
from trino_tpu.connectors.parquet import (
    ParquetConnector,
    write_parquet_table,
)
from trino_tpu.engine import QueryRunner
from trino_tpu.exec import scan_cache
from trino_tpu.memory import ExceededMemoryLimitError
from trino_tpu.metadata import Metadata, Session
from trino_tpu.parallel.core import make_mesh
from trino_tpu.server.fleet import FleetRunner
from trino_tpu.testing.golden import assert_rows_match, to_sqlite

#: test_fleet.py owns 18940+, chaos 18960+, bench 18970-18990+ —
#: storage tests bind 19010+
BASE_PORT = 19010

N_FACT = 200_000
N_DIM = 40


# ---- dataset ---------------------------------------------------------------


def _fact_arrays():
    rng = np.random.default_rng(11)
    k = np.arange(N_FACT, dtype=np.int64) // 100  # sorted: narrow rg stats
    v = rng.integers(0, 1000, N_FACT, dtype=np.int64)
    p = (np.arange(N_FACT, dtype=np.int64) * 13) % 4
    return k, v, p


def _dim_arrays():
    dk = np.arange(400, 400 + N_DIM, dtype=np.int64)
    return dk, dk * 10


def _write_fact(root):
    k, v, p = _fact_arrays()
    write_parquet_table(
        root, "default", "fact",
        TableSchema(
            "fact", [("k", T.BIGINT), ("v", T.BIGINT), ("p", T.BIGINT)]
        ),
        {"k": k, "v": v, "p": p},
        row_group_size=25_000, partition_by=["p"],
    )


@pytest.fixture()
def cold_root(tmp_path):
    """A fact table nobody has scanned: the process-wide scan caches
    (``scan_cache.SHARED``/``SHARED_SPLITS``, ``cache.DEVICE``) key on
    the connector fingerprint — for parquet the ROOT PATH — and are
    shared across connector instances by design, so a test that must
    observe real reads (bytes read, the scan-read fault gate) cannot
    use the module's ``pq_root`` after another test has streamed it."""
    root = str(tmp_path / "cold")
    _write_fact(root)
    return root


@pytest.fixture(scope="module")
def pq_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("pq"))
    _write_fact(root)
    dk, w = _dim_arrays()
    write_parquet_table(
        root, "default", "dim",
        TableSchema("dim", [("k", T.BIGINT), ("w", T.BIGINT)]),
        {"k": dk, "w": w},
    )
    return root


@pytest.fixture(scope="module")
def oracle():
    db = sqlite3.connect(":memory:")
    db.execute("create table fact (k integer, v integer, p integer)")
    k, v, p = _fact_arrays()
    db.executemany(
        "insert into fact values (?,?,?)",
        zip(k.tolist(), v.tolist(), p.tolist()),
    )
    db.execute("create table dim (k integer, w integer)")
    dk, w = _dim_arrays()
    db.executemany(
        "insert into dim values (?,?)", zip(dk.tolist(), w.tolist())
    )
    return db


def check(runner, oracle, sql, abs_tol=1e-9):
    result = runner.execute(sql)
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(
        result.rows, expected, ordered=result.ordered, abs_tol=abs_tol
    )
    return result


AGG_SQL = (
    "select p, count(*), sum(v) from fact "
    "where k >= 1200 and k < 1500 group by p order by p"
)


# ---- local: streamed vs resident vs oracle ---------------------------------


def test_streamed_matches_resident_and_oracle(pq_root, oracle):
    resident = QueryRunner.parquet(pq_root)
    resident.session.properties["streaming_scan_enabled"] = False
    r1 = check(resident, oracle, AGG_SQL)

    streamed = QueryRunner.parquet(pq_root)
    streamed.session.properties["hbm_budget_bytes"] = 1 << 20
    r2 = check(streamed, oracle, AGG_SQL)
    assert [tuple(r) for r in r1.rows] == [tuple(r) for r in r2.rows]
    entry = streamed.executor.scan_log[-1]
    assert entry["streamed"] and entry["batches"] >= 1


def test_streamed_pruning_metrics_and_telemetry(cold_root, oracle):
    runner = QueryRunner.parquet(cold_root)
    runner.session.properties["hbm_budget_bytes"] = 1 << 20
    pruned0 = telemetry.SCAN_ROWGROUPS_PRUNED.total()
    batches0 = telemetry.SCAN_BATCHES.total()
    bytes0 = telemetry.SCAN_BYTES_READ.total()
    check(runner, oracle, AGG_SQL)
    entry = runner.executor.scan_log[-1]
    # k in [1200, 1500) hits rows [120000, 150000) of 200k — the
    # selective predicate must skip whole row groups by footer stats
    assert entry["streamed"] is True
    assert entry["rowgroups_pruned"] > 0
    assert telemetry.SCAN_ROWGROUPS_PRUNED.total() > pruned0
    assert telemetry.SCAN_BATCHES.total() > batches0
    assert telemetry.SCAN_BYTES_READ.total() > bytes0


def test_partition_pruning_in_scan_log(pq_root, oracle):
    runner = QueryRunner.parquet(pq_root)
    runner.session.properties["hbm_budget_bytes"] = 1 << 20
    part0 = telemetry.SCAN_PARTITIONS_PRUNED.total()
    check(
        runner, oracle,
        "select count(*), sum(v) from fact where p = 2",
    )
    entry = runner.executor.scan_log[-1]
    assert entry["partitions_pruned"] == 3
    assert telemetry.SCAN_PARTITIONS_PRUNED.total() >= part0 + 3


def test_explain_analyze_renders_pruning(pq_root):
    runner = QueryRunner.parquet(pq_root)
    runner.session.properties["hbm_budget_bytes"] = 1 << 20
    out = runner.execute("explain analyze " + AGG_SQL)
    text = "\n".join(r[0] for r in out.rows)
    assert "row groups pruned" in text
    assert "streamed in" in text


def test_mesh_streamed_exactness(pq_root, oracle):
    runner = QueryRunner.parquet(pq_root, mesh=make_mesh(8))
    runner.session.properties["hbm_budget_bytes"] = 1 << 20
    check(runner, oracle, AGG_SQL)
    check(
        runner, oracle,
        "select dim.w, count(*), sum(fact.v) from fact "
        "join dim on fact.k = dim.k group by dim.w order by dim.w",
    )


# ---- split-batch cache -----------------------------------------------------


def test_split_batch_cache_lru_and_invalidate():
    cache = scan_cache.SplitBatchCache(max_bytes=1 << 20)

    class _Conn:  # weakref-able stand-in (bare object() is not)
        pass

    conn = _Conn()
    big = {"c": np.zeros(80_000, dtype=np.int64)}  # 640KB
    cache.put(conn, "s", "t", 0, 80_000, ("c",), big)
    assert cache.get(conn, "s", "t", 0, 80_000, ("c",)) is not None
    cache.put(conn, "s", "t", 80_000, 80_000, ("c",), big)
    # second entry evicts the first (byte-bounded LRU)
    assert cache.get(conn, "s", "t", 0, 80_000, ("c",)) is None
    assert cache.get(conn, "s", "t", 80_000, 80_000, ("c",)) is not None
    cache.invalidate(conn, "s", "t")
    assert len(cache) == 0


def test_streamed_scan_warms_split_cache(pq_root, oracle):
    scan_cache.SHARED_SPLITS.clear()
    runner = QueryRunner.parquet(pq_root)
    runner.session.properties["hbm_budget_bytes"] = 1 << 20
    check(runner, oracle, AGG_SQL)
    hits0 = telemetry.SCAN_CACHE_HITS.total()
    check(runner, oracle, AGG_SQL)
    assert telemetry.SCAN_CACHE_HITS.total() > hits0


# ---- memory governance -----------------------------------------------------


def test_over_budget_table_streams_under_cap(tmp_path, oracle):
    """A table ~5x query_max_memory_per_node completes streamed with
    the pool's high-water mark under the cap — and fails loudly with
    the typed error when streaming is disabled."""
    root = str(tmp_path / "big")
    n = 800_000
    rng = np.random.default_rng(5)
    k = np.arange(n, dtype=np.int64)
    v = rng.integers(0, 100, n, dtype=np.int64)
    g = k % 7
    write_parquet_table(
        root, "default", "big",
        TableSchema(
            "big", [("k", T.BIGINT), ("v", T.BIGINT), ("g", T.BIGINT)]
        ),
        {"k": k, "v": v, "g": g},
        row_group_size=100_000,
    )
    db = sqlite3.connect(":memory:")
    db.execute("create table big (k integer, v integer, g integer)")
    db.executemany(
        "insert into big values (?,?,?)",
        zip(k.tolist(), v.tolist(), g.tolist()),
    )
    sql = "select g, count(*), sum(v) from big group by g order by g"
    cap = "4MB"  # scanned bytes = 800k rows x 24B ~ 19MB >= 4x cap

    runner = QueryRunner.parquet(root)
    runner.session.properties["query_max_memory_per_node"] = cap
    result = runner.execute(sql)
    expected = db.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(result.rows, expected, ordered=result.ordered)
    assert runner.executor.scan_log[-1]["streamed"] is True
    peak = runner.executor.memory_pool.peak_bytes
    assert 0 < peak <= runner.executor._per_node_cap(), peak

    off = QueryRunner.parquet(root)
    off.session.properties["query_max_memory_per_node"] = cap
    off.session.properties["streaming_scan_enabled"] = False
    with pytest.raises(ExceededMemoryLimitError):
        off.execute(sql)


# ---- a table several times the HBM budget (the storage CI lane's asserts) ---

N_EVENTS = 600_000
#: tight enough that the scan MUST stream (scanned bytes are ~14 MB)
EVENTS_BUDGET = 8 << 20


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    """600 k rows in 100 k-row groups, partitioned four ways, ``k``
    sorted so that every row group's footer range is narrow; a runner
    held to an 8 MiB HBM budget; and the columns, for a numpy answer."""
    root = str(tmp_path_factory.mktemp("events"))
    rng = np.random.default_rng(7)
    k = np.arange(N_EVENTS, dtype=np.int64)
    v = rng.integers(0, 1000, N_EVENTS, dtype=np.int64)
    p = (k * 13) % 4
    write_parquet_table(
        root, "default", "events",
        TableSchema(
            "events", [("k", T.BIGINT), ("v", T.BIGINT), ("p", T.BIGINT)]
        ),
        {"k": k, "v": v, "p": p},
        row_group_size=100_000, partition_by=["p"],
    )
    runner = QueryRunner.parquet(root)
    runner.session.properties["hbm_budget_bytes"] = EVENTS_BUDGET
    return runner, k, v, p


def _by_partition(v, p, keep):
    return [
        (int(g), int((keep & (p == g)).sum()), int(v[keep & (p == g)].sum()))
        for g in range(4) if (keep & (p == g)).any()
    ]


def test_streamed_scan_peak_stays_under_hbm_budget(events):
    runner, k, v, p = events
    rows = runner.execute(
        "select p, count(*), sum(v) from events group by p order by p"
    ).rows
    assert [tuple(r) for r in rows] == _by_partition(
        v, p, np.ones(N_EVENTS, dtype=bool)
    )
    entry = runner.executor.scan_log[-1]
    assert entry["streamed"] is True and entry["batches"] > 1
    peak = int(runner.executor.memory_pool.peak_bytes)
    assert 0 < peak <= EVENTS_BUDGET, peak


def test_selective_scan_prunes_rowgroups_of_a_large_table(events):
    runner, k, v, p = events
    lo, hi = int(N_EVENTS * 0.50), int(N_EVENTS * 0.55)
    rows = runner.execute(
        "select p, count(*), sum(v) from events "
        f"where k >= {lo} and k < {hi} group by p order by p"
    ).rows
    assert [tuple(r) for r in rows] == _by_partition(
        v, p, (k >= lo) & (k < hi)
    )
    entry = runner.executor.scan_log[-1]
    # 5 % of a sorted key sits in the first row group of each
    # partition file: the second goes by its footer range, before a
    # page is decoded
    assert entry["rowgroups_total"] == 8, entry
    assert entry["rowgroups_pruned"] == 4, entry


# ---- chaos: split-granular read retry --------------------------------------


def test_scan_read_chaos_retries_at_split_granularity(tmp_path):
    from trino_tpu.testing.chaos import run_storage_chaos

    rec = run_storage_chaos(seed=3, root=str(tmp_path / "chaos"))
    # every fired injection retried in place: attempts 0 and 1 per tag
    attempts = {}
    for site, tag, attempt, _kind in rec["fired"]:
        assert site == "scan-read"
        attempts.setdefault(tag, set()).add(attempt)
    assert attempts and all(a == {0, 1} for a in attempts.values())


def test_scan_read_exhaustion_fails(cold_root):
    from trino_tpu.exec.stream_scan import SCAN_READ_ATTEMPTS

    runner = QueryRunner.parquet(cold_root)
    runner.session.properties["hbm_budget_bytes"] = 1 << 20
    inj = fault.FaultInjector(seed=0)
    inj.arm("scan-read", times=SCAN_READ_ATTEMPTS)
    fault.activate(inj)
    try:
        with pytest.raises(fault.InjectedFault):
            runner.execute(AGG_SQL)
    finally:
        fault.deactivate()


# ---- connector-level pushdown ----------------------------------------------


def test_splits_carry_stats_and_prune(pq_root):
    conn = ParquetConnector(pq_root)
    splits = conn.splits("default", "fact", 8)
    assert sum(s.count for s in splits) == N_FACT
    assert all(s.stats for s in splits)
    m = dict(conn.scan_metrics)
    # 4 partitions x 50k rows / 25k per row group = 8 row groups
    assert m["rowgroups_total"] == 8
    # a selective domain prunes both partitions and row groups
    dom = {"p": ColumnDomain(2, 2), "k": ColumnDomain(100, 150)}
    pruned = conn.splits("default", "fact", 8, domains=dom)
    assert sum(s.count for s in pruned) < N_FACT
    m = dict(conn.scan_metrics)
    assert m["partitions_pruned"] == 3
    assert m["rowgroups_pruned"] > 0
    # Split.disjoint agrees with the connector's own stats pruning
    assert all(not s.disjoint(dom) for s in pruned)


# ---- long decimals ---------------------------------------------------------


def test_decimal38_two_limb_roundtrip(tmp_path):
    """precision > 18 columns read into the engine's two-limb [n, 2]
    layout and reconstruct exactly — including an exact SUM."""
    import decimal

    import pyarrow as pa
    import pyarrow.parquet as pq

    root = str(tmp_path / "dec")
    os.makedirs(f"{root}/s")
    vals = [
        decimal.Decimal("12345678901234567890123.45"),
        decimal.Decimal("-98765432109876543210.99"),
        decimal.Decimal("0.01"),
        None,
    ]
    pq.write_table(
        pa.table({
            "k": pa.array([1, 2, 3, 4], type=pa.int64()),
            "d": pa.array(vals, type=pa.decimal128(38, 2)),
        }),
        f"{root}/s/t.parquet",
    )
    md = Metadata()
    md.register_catalog("hive", ParquetConnector(root))
    runner = QueryRunner(md, Session(catalog="hive", schema="s"))
    rows = runner.execute("select k, d from t order by k").rows
    assert [r[1] for r in rows] == vals
    total = runner.execute("select sum(d) from t").rows
    assert total == [(sum(v for v in vals if v is not None),)]


# ---- fleet: distributed scans + coordinator dynamic filtering --------------


def _spawn_worker(port, root):
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "trino_tpu.server.worker",
            "--port", str(port), "--parquet-root", root,
            "--schema", "default",
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
def storage_workers(pq_root):
    procs = [_spawn_worker(BASE_PORT + i, pq_root) for i in range(2)]
    yield [f"http://127.0.0.1:{BASE_PORT + i}" for i in range(2)]
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


@pytest.fixture()
def storage_fleet(storage_workers, pq_root, tmp_path):
    md = Metadata()
    md.register_catalog("hive", ParquetConnector(pq_root))
    return FleetRunner(
        storage_workers, md, Session(catalog="hive", schema="default"),
        spool_root=str(tmp_path / "spool"), n_partitions=4,
    )


def test_fleet_storage_scan_exactness(storage_fleet, oracle):
    check(storage_fleet, oracle, AGG_SQL)


def test_fleet_dynamic_filter_narrows_probe_scan(storage_fleet, oracle):
    """The dim build's key range must reach the fact scan's domains
    BEFORE its row groups are read: df_scan_log records the injected
    [400, 439] domain, and the result stays oracle-exact."""
    check(
        storage_fleet, oracle,
        "select dim.w, count(*), sum(fact.v) from fact "
        "join dim on fact.k = dim.k group by dim.w order by dim.w",
    )
    assert storage_fleet.df_scan_log, "coordinator DF never fired"
    entry = storage_fleet.df_scan_log[-1]
    assert entry["table"] == "default.fact"
    assert entry["columns"]["k"] == [400, 400 + N_DIM - 1]


def test_fleet_dynamic_filter_drops_probe_rows(storage_fleet, oracle):
    """With DF on, the probe-side tasks read only the row groups whose
    k-range intersects the dim keys — visible as fewer input rows into
    the join stage than the full fact table."""
    check(
        storage_fleet, oracle,
        "select count(*) from fact join dim on fact.k = dim.k",
    )
    assert storage_fleet.df_scan_log
    # the probe scan's split tasks cover a narrowed row range: their
    # total output is far below the full table (row-group granularity
    # still over-approximates the exact key range, so not exact-count)
    rows = sum(
        t["rows_out"] for t in storage_fleet._task_stats
        if t["state"] == "FINISHED"
    )
    assert rows < N_FACT
