"""Fleet x mesh composition: worker processes that each OWN a device
mesh execute stage fragments SPMD over their local devices.

The pod shape of the reference's worker=node model (SURVEY §5.8): the
durable spooled exchange is the DCN tier between workers; inside each
worker the fragment re-partitions over ICI collectives. VERDICT r4
weak #3: the two distribution layers must compose — plan partitioning
uses the REAL per-worker device count discovered from /v1/info, and
the kill -9 recovery path runs against mesh-owning workers.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.metadata import Metadata, Session
from trino_tpu.server.fleet import FleetRunner
from trino_tpu.testing.golden import (
    assert_rows_match,
    load_tpch_sqlite,
    to_sqlite,
)

BASE_PORT = 19140
MESH_DEVICES = 4


def _spawn_mesh_worker(port: int) -> subprocess.Popen:
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={MESH_DEVICES}"
    )
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "trino_tpu.server.worker",
            "--port", str(port), "--mesh",
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    deadline = time.monotonic() + 180
    while True:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/info", timeout=1
            ) as resp:
                info = json.loads(resp.read())
                if not (info["mesh"] and info["devices"] == MESH_DEVICES):
                    proc.kill()  # don't leak a half-configured worker
                    raise RuntimeError(f"bad worker config: {info}")
                return proc
        except (OSError, ValueError):
            if proc.poll() is not None:
                raise RuntimeError(
                    f"worker died: {proc.stdout.read()[:4000]}"
                )
            if time.monotonic() > deadline:
                proc.kill()
                raise TimeoutError("mesh worker did not come up")
            time.sleep(0.3)


@pytest.fixture(scope="module")
def workers():
    procs = [_spawn_mesh_worker(BASE_PORT + i) for i in range(2)]
    yield [f"http://127.0.0.1:{BASE_PORT + i}" for i in range(2)]
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


@pytest.fixture(scope="module")
def spool_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("spool_mesh"))


@pytest.fixture()
def fleet(workers, spool_root):
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    return FleetRunner(
        workers, md, Session(catalog="tpch", schema="tiny"),
        spool_root=spool_root, n_partitions=3,
    )


@pytest.fixture(scope="module")
def oracle():
    data = QueryRunner.tpch("tiny").metadata.connector("tpch").data("tiny")
    return load_tpch_sqlite(data)


def check(fleet, oracle, sql, abs_tol=1e-9):
    result = fleet.execute(sql)
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(
        result.rows, expected, ordered=result.ordered, abs_tol=abs_tol
    )
    return result


def test_planner_sees_fleet_parallelism(fleet):
    """Discovery: plan shard count = spool partitions x per-worker
    device count (no _FakeMesh constant)."""
    assert set(fleet.worker_devices.values()) == {MESH_DEVICES}
    assert fleet._planner.mesh.devices.size == 3 * MESH_DEVICES


def test_mesh_fleet_aggregation(fleet, oracle):
    """PARTIAL agg on split scans -> hash spool -> FINAL agg on a
    mesh worker whose shards re-exchange the partition locally."""
    check(
        fleet, oracle,
        "select l_returnflag, l_linestatus, sum(l_quantity), count(*) "
        "from lineitem group by l_returnflag, l_linestatus order by 1, 2",
    )


def test_mesh_fleet_high_cardinality_group(fleet, oracle):
    """Many groups per spool partition: local re-exchange must keep
    every key on exactly one shard or FINAL counts double."""
    check(
        fleet, oracle,
        "select l_orderkey, sum(l_quantity) q from lineitem "
        "group by l_orderkey order by q desc, l_orderkey limit 20",
        abs_tol=1e-6,
    )


def test_mesh_fleet_partitioned_join(fleet, oracle):
    fleet.session.properties["join_distribution_type"] = "PARTITIONED"
    check(
        fleet, oracle,
        "select c_name, sum(o_totalprice) t from customer, orders "
        "where c_custkey = o_custkey group by c_name "
        "order by t desc limit 10",
        abs_tol=1e-6,
    )


def test_mesh_fleet_three_way_join_minimal_repro(fleet, oracle):
    """The smallest shape of the q3/q5/q9 family: customer⋈orders⋈
    lineitem hash-partitioned on the mesh — no filters, no date
    arithmetic, plain sum/group/limit."""
    fleet.session.properties["join_distribution_type"] = "PARTITIONED"
    # debug assertion (plan.validate): count rows across every
    # exchange edge so a failure names the edge that dropped rows
    # (mesh collective or fleet spool edge) instead of just producing
    # a wrong row set
    fleet.session.properties["check_exchange_coverage"] = True
    check(
        fleet, oracle,
        "select o_orderkey, sum(l_extendedprice) rev "
        "from customer, orders, lineitem "
        "where c_custkey = o_custkey and l_orderkey = o_orderkey "
        "group by o_orderkey order by rev desc, o_orderkey limit 10",
        abs_tol=0.01,
    )


def test_mesh_fleet_tpch_q3(fleet, oracle):
    from trino_tpu.connectors.tpch.queries import QUERIES

    check(fleet, oracle, QUERIES["q03"], abs_tol=0.006)


def test_mesh_fleet_tpch_q18(fleet, oracle):
    from trino_tpu.connectors.tpch.queries import QUERIES

    check(fleet, oracle, QUERIES["q18"], abs_tol=0.006)


def test_mesh_fleet_survives_worker_kill9(workers, spool_root, oracle):
    """kill -9 a MESH-OWNING worker mid-query: retry from spooled
    inputs on the surviving mesh worker, oracle-exact results."""
    victim_port = BASE_PORT + 7
    victim = _spawn_mesh_worker(victim_port)
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    fleet = FleetRunner(
        [f"http://127.0.0.1:{victim_port}"] + list(workers),
        md, Session(catalog="tpch", schema="tiny"),
        spool_root=spool_root, n_partitions=3,
    )
    fleet.session.properties["fleet_task_delay_ms"] = 300
    state = {"killed": False, "waves_done": 0}

    def stage_hook(stage_id):
        state["waves_done"] += 1

    def post_hook(stage_id, task_id, w):
        if (
            state["waves_done"] > 0
            and not state["killed"]
            and str(victim_port) in w.uri
        ):
            os.kill(victim.pid, signal.SIGKILL)
            state["killed"] = True

    fleet.stage_hook = stage_hook
    fleet.post_hook = post_hook
    sql = (
        "select l_returnflag, l_linestatus, sum(l_quantity), "
        "avg(l_extendedprice), count(*) from lineitem "
        "group by l_returnflag, l_linestatus order by 1, 2"
    )
    result = fleet.execute(sql)
    assert state["killed"], "victim worker was never scheduled past wave 1"
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(
        result.rows, expected, ordered=result.ordered, abs_tol=0.006
    )
