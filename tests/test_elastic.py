"""Elastic fleet: live membership, graceful drain, scale-down-safe
scheduling.

Unit tier (fake clock, no processes): the MembershipRegistry TTL state
machine — join, flap damping (a bouncing worker is neither evicted nor
double-admitted), damped eviction + re-admission, GONE expiry, the
drain deregistration gate on residency pins, ClusterSizeMonitor's
park-then-typed-reject, and the announce fault seams.

Fleet tier (real worker processes): graceful drain mid-query completes
byte-identical with ``tasks_retried == 0``; hard-killing a DRAINING
worker still recovers through the existing FTE crash path; a worker
announced *after* dispatch live-joins the same query and receives
later-stage tasks; dispatch against ``< min_workers`` parks, then
rejects typed (INSUFFICIENT_RESOURCES) with a membership line in the
post-mortem bundle.
"""

import json
import threading
import time
import urllib.request

import pytest

from trino_tpu import fault, telemetry, tracker
from trino_tpu.membership import (
    ClusterSizeMonitor,
    InsufficientResourcesError,
    MembershipRegistry,
    announce_once,
)
from trino_tpu.server.coordinator import error_payload
from trino_tpu.testing import chaos
from trino_tpu.testing.golden import (
    assert_rows_match,
    load_tpch_sqlite,
    to_sqlite,
)

BASE_PORT = 19320

_SQL = (
    "select c_mktsegment, count(*), sum(o_totalprice) "
    "from customer, orders where c_custkey = o_custkey "
    "group by c_mktsegment order by 1"
)


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    fault.deactivate()


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _registry(**kw):
    clk = _Clock()
    kw.setdefault("ttl_s", 1.0)
    kw.setdefault("damping_s", 0.5)
    kw.setdefault("gone_after_s", 3.0)
    reg = MembershipRegistry(clock=clk, **kw)
    joins, leaves = [], []
    reg.on_join.append(lambda m: joins.append(m.node_id))
    reg.on_leave.append(lambda m, r: leaves.append((m.node_id, r)))
    return reg, clk, joins, leaves


# ---- registry state machine (unit, fake clock) ---------------------


def test_join_records_transition_and_fires_on_join():
    reg, clk, joins, leaves = _registry()
    resp = reg.announce("w0", "http://h:1/")
    assert resp == {"state": "ACTIVE", "ttl_s": 1.0, "deregister": False}
    assert joins == ["w0"] and leaves == []
    (m,) = reg.schedulable()
    assert m.node_id == "w0" and m.uri == "http://h:1"
    t = reg.transitions()[-1]
    assert (t.src, t.dst, t.reason) == ("GONE", "ACTIVE", "join")


def test_flap_damping_not_evicted_not_double_admitted():
    """A worker bouncing active<->inactive inside the damping window
    never leaves the schedulable set: no on_leave churn, and its
    re-announce fires no on_join (no double admission)."""
    reg, clk, joins, leaves = _registry()
    reg.announce("w0", "http://h:1")
    for _ in range(3):  # three bounce cycles
        clk.advance(1.2)  # past ttl_s=1.0 -> INACTIVE...
        reg.sweep()
        (m,) = reg.members()
        assert m.state == "INACTIVE" and not m.evicted
        # ...but still inside damping_s=0.5, so still schedulable
        assert [s.node_id for s in reg.schedulable()] == ["w0"]
        clk.advance(0.2)  # re-announce within the window
        reg.announce("w0", "http://h:1")
        (m,) = reg.schedulable()
        assert m.state == "ACTIVE"
    assert joins == ["w0"]  # the initial join only — never re-fired
    assert leaves == []  # never evicted
    assert reg.members()[0].flaps == 3


def test_damped_eviction_then_readmission():
    reg, clk, joins, leaves = _registry()
    reg.announce("w0", "http://h:1")
    clk.advance(1.2)
    reg.sweep()  # INACTIVE, damping window opens
    assert leaves == []
    clk.advance(0.6)  # past damping_s=0.5
    reg.sweep()
    assert leaves == [("w0", "heartbeat lost")]
    assert reg.schedulable() == []  # evicted, but still tracked
    assert reg.members()[0].state == "INACTIVE"
    reg.announce("w0", "http://h:1")  # really back -> re-admit
    assert joins == ["w0", "w0"]
    assert [m.node_id for m in reg.schedulable()] == ["w0"]


def test_inactive_expires_to_gone():
    reg, clk, joins, leaves = _registry()
    reg.announce("w0", "http://h:1")
    clk.advance(1.2)
    reg.sweep()
    clk.advance(3.5)  # past gone_after_s=3.0 of INACTIVE quiet
    reg.sweep()
    assert reg.members() == []
    t = reg.transitions()[-1]
    assert (t.dst, t.reason) == ("GONE", "expired")
    # a fresh announce after GONE is a brand-new join
    reg.announce("w0", "http://h:1")
    assert joins.count("w0") >= 2


def test_drain_deregisters_only_when_unpinned():
    """DRAINING -> unschedulable-but-alive; DRAINED deregisters only
    once no residency provider still pins the worker's buffers."""
    reg, clk, joins, leaves = _registry()
    pins = {"http://h:1"}
    reg.residency_providers.append(lambda: pins)
    reg.announce("w0", "http://h:1")
    resp = reg.announce("w0", "http://h:1", state="DRAINING",
                        active_tasks=2)
    assert resp["deregister"] is False and resp["state"] == "DRAINING"
    assert leaves == [("w0", "drain")]
    assert reg.schedulable() == []  # no new tasks
    assert reg.members()[0].state == "DRAINING"  # ...but alive
    # tasks finished, yet a consumer still pins an exchange buffer
    clk.advance(0.2)
    resp = reg.announce("w0", "http://h:1", state="DRAINED",
                        active_tasks=0)
    assert resp["deregister"] is False
    assert reg.members()[0].state == "DRAINED"
    pins.clear()  # last dependent consumer committed
    clk.advance(0.2)
    resp = reg.announce("w0", "http://h:1", state="DRAINED",
                        active_tasks=0)
    assert resp["deregister"] is True and resp["state"] == "GONE"
    assert reg.members() == []
    t = reg.transitions()[-1]
    assert (t.src, t.dst) == ("DRAINED", "GONE")
    assert "trino_drain_duration_seconds" in telemetry.render_prometheus()


def test_draining_worker_that_stops_heartbeating_expires():
    """A drain that stops announcing is a crash, not a drain: the TTL
    tiers expire it instead of waiting on deregistration forever."""
    reg, clk, joins, leaves = _registry()
    reg.announce("w0", "http://h:1")
    reg.announce("w0", "http://h:1", state="DRAINING", active_tasks=1)
    clk.advance(3.5)  # silence past gone_after_s
    reg.sweep()
    assert reg.members() == []
    t = reg.transitions()[-1]
    assert (t.dst, t.reason) == ("GONE", "died while draining")


def test_membership_telemetry_emitted():
    reg, clk, joins, leaves = _registry()
    reg.announce("w0", "http://h:1")
    text = telemetry.render_prometheus()
    assert "trino_membership_transitions_total" in text
    assert "trino_cluster_workers" in text


def test_snapshot_is_jsonable():
    reg, clk, joins, leaves = _registry()
    reg.announce("w0", "http://h:1")
    reg.announce("w1", "http://h:2", state="DRAINING")
    snap = json.loads(json.dumps(reg.snapshot()))
    assert {m["node_id"] for m in snap["members"]} == {"w0", "w1"}
    assert snap["transitions"][-1]["to"] == "DRAINING"


# ---- size gating (unit) --------------------------------------------


def test_cluster_size_monitor_parks_then_rejects_typed():
    reg, clk, joins, leaves = _registry()
    mon = ClusterSizeMonitor(reg, 1, poll_s=0.01)
    t0 = time.monotonic()
    with pytest.raises(InsufficientResourcesError, match="requires 1"):
        mon.wait_for_minimum(timeout_s=0.15)
    assert time.monotonic() - t0 >= 0.15  # parked, not fail-fast
    reg.announce("w0", "http://h:1")
    assert mon.wait_for_minimum(timeout_s=0.15) == 1


def test_cluster_size_monitor_unparks_on_join():
    reg, clk, joins, leaves = _registry()
    threading.Timer(
        0.1, lambda: reg.announce("w0", "http://h:1")
    ).start()
    assert ClusterSizeMonitor(
        reg, 1, poll_s=0.01
    ).wait_for_minimum(timeout_s=5.0) == 1


def test_insufficient_resources_maps_to_error_code_134():
    p = error_payload("InsufficientResourcesError: 0 of 2 workers")
    assert p["errorCode"] == 134
    assert p["errorName"] == "INSUFFICIENT_RESOURCES"


# ---- announce fault seams (unit) -----------------------------------


def test_membership_fault_sites_registered():
    assert "heartbeat-loss" in fault.SITES
    assert "announce-drop" in fault.SITES


def test_announce_drop_fires_on_initial_announce_only():
    inj = fault.FaultInjector()
    inj.arm("announce-drop", times=1)
    fault.activate(inj)
    with pytest.raises(fault.InjectedFault):
        announce_once("http://127.0.0.1:1", "w0", "http://h:1",
                      initial=True, attempt=0)
    assert inj.injected == [("w0", 0)]


def test_heartbeat_loss_respects_attempt_schedule():
    """``times=1`` drops exactly the first heartbeat round; the next
    round passes the seam (and then fails on transport — nothing is
    listening — which is precisely the miss the TTL machine absorbs)."""
    inj = fault.FaultInjector()
    inj.arm("heartbeat-loss", times=1)
    fault.activate(inj)
    with pytest.raises(fault.InjectedFault):
        announce_once("http://127.0.0.1:1", "w0", "http://h:1",
                      attempt=0, timeout_s=0.2)
    with pytest.raises(Exception) as ei:
        announce_once("http://127.0.0.1:1", "w0", "http://h:1",
                      attempt=1, timeout_s=0.2)
    assert not isinstance(ei.value, fault.InjectedFault)


# ---- fleet tier: real worker processes -----------------------------


@pytest.fixture(scope="module")
def cluster():
    """(procs, uris) for 5 workers in one boot wave: uris[0:3] are the
    shared never-mutated pool, uris[3] the drain target, uris[4] the
    kill target (each destructive test owns its own worker)."""
    procs, uris = chaos.spawn_workers(5, base_port=BASE_PORT, platform="cpu")
    yield procs, uris
    chaos.stop_workers(procs)


@pytest.fixture(scope="module")
def workers(cluster):
    return cluster[1][:3]


@pytest.fixture(scope="module")
def spool_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("elastic-spool"))


@pytest.fixture(scope="module")
def oracle():
    from trino_tpu.engine import QueryRunner

    data = QueryRunner.tpch("tiny").metadata.connector("tpch").data("tiny")
    return load_tpch_sqlite(data)


def _drain(uri: str) -> dict:
    req = urllib.request.Request(
        uri.rstrip("/") + "/v1/drain", data=b"{}", method="POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=5) as resp:
        return json.loads(resp.read().decode())


def _worker_state(uri: str) -> str:
    with urllib.request.urlopen(uri + "/v1/info", timeout=5) as resp:
        return json.loads(resp.read().decode())["state"]


def _fast_retries(fleet):
    fleet.session.properties.update({
        "speculation_enabled": False,
        "retry_backoff_seed": 7,
        "retry_initial_delay_ms": 5,
        "retry_max_delay_ms": 20,
    })
    return fleet


def test_graceful_drain_zero_retries(cluster, spool_root, oracle):
    """Draining a worker mid-query is not a failure: running tasks
    finish, buffers keep serving, the result is byte-identical to the
    undrained run and nothing is retried."""
    _, uris = cluster
    fleet_uris = [uris[0], uris[1], uris[3]]
    clean = _fast_retries(
        chaos.make_fleet(fleet_uris, spool_root)
    ).execute(_SQL)

    fleet = _fast_retries(chaos.make_fleet(fleet_uris, spool_root))
    drained = []

    def drain_on_first_post(stage_id, task_id, worker):
        if not drained:
            drained.append(_drain(uris[3]))

    fleet.post_hook = drain_on_first_post
    res = fleet.execute(_SQL)
    assert drained, "post_hook never fired"
    assert res.rows == clean.rows  # byte-identical
    assert_rows_match(
        res.rows, oracle.execute(to_sqlite(_SQL)).fetchall(),
        ordered=res.ordered, abs_tol=1e-6,
    )
    assert res.tasks_retried == 0
    # drained worker is unschedulable-but-ALIVE, still serving
    assert _worker_state(uris[3]) in ("DRAINING", "DRAINED")


def test_kill_draining_worker_recovers_via_fte(cluster, spool_root,
                                               oracle):
    """Hard-killing a DRAINING worker is a crash like any other: the
    poll evicts it and task retry from spool recovers the query."""
    procs, uris = cluster
    fleet = _fast_retries(
        chaos.make_fleet([uris[0], uris[1], uris[4]], spool_root)
    )
    killed = []

    def drain_then_kill(stage_id, task_id, worker):
        # fire only when a post lands ON the target, so it dies with
        # that task in flight — a guaranteed FTE retry
        if worker.uri == uris[4] and not killed:
            killed.append(task_id)
            _drain(uris[4])
            procs[4].kill()

    fleet.post_hook = drain_then_kill
    res = fleet.execute(_SQL)
    assert killed
    assert_rows_match(
        res.rows, oracle.execute(to_sqlite(_SQL)).fetchall(),
        ordered=res.ordered, abs_tol=1e-6,
    )
    assert res.tasks_retried >= 1  # the crash path, exercised


def test_live_join_receives_later_stage_tasks(workers, spool_root,
                                              oracle):
    """A worker announced after dispatch joins the live cluster and
    receives tasks for a later stage of the SAME query."""
    reg = MembershipRegistry(ttl_s=60.0)
    fleet = _fast_retries(
        chaos.make_fleet(workers[:2], spool_root, membership=reg)
    )
    announced = []

    def announce_third(stage_id):
        if not announced:
            reg.announce("late-worker", workers[2])
            announced.append(stage_id)

    fleet.stage_hook = announce_third
    res = fleet.execute(_SQL)
    assert announced, "stage_hook never fired"
    assert_rows_match(
        res.rows, oracle.execute(to_sqlite(_SQL)).fetchall(),
        ordered=res.ordered, abs_tol=1e-6,
    )
    assert fleet.stats.get("workers_joined", 0) >= 1
    late = workers[2].rstrip("/")
    ran_on_late = {
        ts["stage_id"] for ts in res.task_stats
        if ts.get("worker") == late
    }
    assert ran_on_late, "live-joined worker never received a task"


def test_min_workers_parks_then_rejects_with_bundle(workers,
                                                    spool_root):
    """Dispatch against < min_workers parks for the wait budget, then
    fails typed — and the post-mortem bundle carries the membership
    snapshot that explains why."""
    reg = MembershipRegistry(ttl_s=60.0)
    reg.announce("w0", workers[0])
    fleet = chaos.make_fleet(
        workers[:1], spool_root, membership=reg,
        min_workers=2, min_workers_wait_s=0.3,
    )
    qid = "elastic-minrej-1"
    t0 = time.monotonic()
    with pytest.raises(InsufficientResourcesError):
        fleet.execute(_SQL, query_id=qid)
    assert time.monotonic() - t0 >= 0.3
    bundle = tracker.QUERY_INFO.get_diagnostics(qid)
    assert bundle is not None
    snap = bundle.get("membership")
    assert snap and {m["node_id"] for m in snap["members"]} == {"w0"}


def test_min_workers_proceeds_once_met(workers, spool_root, oracle):
    """The park is a wait, not a rejection: a second worker announcing
    mid-park unblocks dispatch and the query completes normally."""
    reg = MembershipRegistry(ttl_s=60.0)
    reg.announce("w0", workers[0])
    fleet = _fast_retries(chaos.make_fleet(
        workers[:2], spool_root, membership=reg,
        min_workers=2, min_workers_wait_s=5.0,
    ))
    threading.Timer(
        0.2, lambda: reg.announce("w1", workers[1])
    ).start()
    res = fleet.execute(_SQL)
    assert_rows_match(
        res.rows, oracle.execute(to_sqlite(_SQL)).fetchall(),
        ordered=res.ordered, abs_tol=1e-6,
    )


# ---- coordinator announce endpoint + nodes table -------------------


def test_announce_endpoint_and_nodes_table():
    """PUT /v1/announce feeds the coordinator registry over the wire;
    system.runtime.nodes reports membership state + heartbeat age."""
    from trino_tpu.engine import QueryRunner
    from trino_tpu.server import Coordinator

    runner = QueryRunner.tpch("tiny")
    c = Coordinator(runner).start()
    try:
        resp = announce_once(
            c.uri, "wire-worker", "http://127.0.0.1:9", initial=True
        )
        assert resp["state"] == "ACTIVE" and resp["deregister"] is False
        assert c.membership.heartbeat_age("wire-worker") is not None
        res = runner.execute(
            "select node_id, state, heartbeat_age_s "
            "from system.runtime.nodes"
        )
        by_id = {r[0]: r for r in res.rows}
        assert by_id["wire-worker"][1] == "ACTIVE"
        assert by_id["wire-worker"][2] >= 0.0
        assert "local-0" in by_id  # the coordinator itself
    finally:
        c.stop()


def test_worker_announcer_joins_and_drain_deregisters():
    """The full loop: a worker booted with --coordinator announces
    itself, heartbeats, and after a drain reports DRAINED and
    deregisters (announce loop told {"deregister": true})."""
    from trino_tpu.engine import QueryRunner
    from trino_tpu.server import Coordinator

    c = Coordinator(QueryRunner.tpch("tiny")).start()
    procs = []
    try:
        import os
        import subprocess
        import sys

        env = os.environ.copy()
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        port = BASE_PORT + 5
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "trino_tpu.server.worker",
             "--port", str(port), "--coordinator", c.uri,
             "--node-id", "announcer-w0"],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        ))
        uri = f"http://127.0.0.1:{port}"
        deadline = time.monotonic() + 120
        while c.membership.heartbeat_age("announcer-w0") is None:
            assert time.monotonic() < deadline, "worker never announced"
            time.sleep(0.2)
        (m,) = c.membership.members()
        assert m.state == "ACTIVE" and m.uri == uri
        _drain(uri)
        deadline = time.monotonic() + 30
        while c.membership.members():
            assert time.monotonic() < deadline, "drain never deregistered"
            time.sleep(0.2)
        dst = [t.dst for t in c.membership.transitions()]
        assert dst[-1] == "GONE" and "DRAINING" in dst
    finally:
        chaos.stop_workers(procs)
        c.stop()
