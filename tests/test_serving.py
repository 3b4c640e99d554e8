"""Multi-query serving layer: fair-share admission, shared slot pool,
O(workers) RPC polling, cross-query isolation.

The analog of the reference's DispatchManager + resource-group serving
path under real concurrency: MANY statements in flight at once over
ONE 2-worker fleet, every result checked row-for-row against the
sqlite oracle (concurrency that corrupts answers is the failure mode
that matters most). The suite covers the four serving contracts:

- correctness: >=16 statements from >=8 client threads, embedded
  (ServingRunner.execute) and through the HTTP statement protocol,
  all oracle-exact;
- fairness: a weight-1 group's query completes while a weight-8 group
  keeps the fleet saturated (deficit round-robin visits every
  backlogged group each round — no starvation);
- scalability: coordinator-side RPC-poll threads stay O(workers) as
  the live-query count grows;
- isolation: an injected task failure in one query retries without
  perturbing a concurrently-running query (both oracle-exact, the
  untouched query retries nothing).

Port discipline: serving tests own 19020+ (test_fleet 18940+, chaos
18960+, bench serving 18970+, bench chaos 18980+, telemetry 19000+).
"""

import json
import threading
import time
import urllib.request

import pytest

from trino_tpu.engine import QueryRunner
from trino_tpu.server.resource_groups import (
    ResourceGroup,
    ResourceGroupManager,
)
from trino_tpu.testing import chaos as chaos_mod
from trino_tpu.testing.golden import (
    assert_rows_match,
    load_tpch_sqlite,
    to_sqlite,
)

BASE_PORT = 19020

#: fast tiny-schema statements with distinct shapes (scan+agg, join,
#: order-by projection) — cheap enough that 8 threads x 2+ statements
#: stay inside the tier-1 wall-clock budget
MIX = [
    "select count(*) from orders",
    "select o_orderpriority, count(*) from orders "
    "group by o_orderpriority order by 1",
    "select c_mktsegment, count(*), sum(o_totalprice) "
    "from customer, orders where c_custkey = o_custkey "
    "group by c_mktsegment order by 1",
    "select r_name from region order by r_name",
]


@pytest.fixture(scope="module")
def workers():
    procs, uris = chaos_mod.spawn_workers(2, base_port=BASE_PORT, platform="cpu")
    yield uris
    chaos_mod.stop_workers(procs)


@pytest.fixture(scope="module")
def spool_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("serving-spool"))


@pytest.fixture(scope="module")
def oracle():
    data = QueryRunner.tpch("tiny").metadata.connector("tpch").data("tiny")
    return load_tpch_sqlite(data)


@pytest.fixture()
def serving(workers, spool_root):
    s = chaos_mod.make_serving(workers, spool_root)
    yield s
    s.stop()


@pytest.fixture(scope="module")
def expected(oracle):
    """Oracle rows per MIX statement, computed ON THE MAIN THREAD:
    sqlite connections are single-thread objects, so client threads
    compare against this precomputed dict instead of querying."""
    return {
        sql: oracle.execute(to_sqlite(sql)).fetchall() for sql in MIX
    }


def _run_clients(serving, expected, n_threads, per_thread, user=None):
    """Drive ``n_threads`` closed-loop clients; every statement's rows
    are asserted against the oracle on its own thread. Returns the
    list of per-statement errors (empty = all exact)."""
    errors = []

    def client(cid):
        try:
            for i in range(per_thread):
                sql = MIX[(cid + i) % len(MIX)]
                res = serving.execute(sql, user=user)
                assert_rows_match(
                    res.rows, expected[sql],
                    ordered=res.ordered, abs_tol=1e-6,
                )
        except Exception as e:
            errors.append(f"client {cid}: {type(e).__name__}: {e}")

    threads = [
        threading.Thread(target=client, args=(c,))
        for c in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return errors


def test_concurrent_statements_oracle_exact(serving, expected):
    # >=16 statements from >=8 threads, one shared fleet, all exact
    errors = _run_clients(serving, expected, n_threads=8, per_thread=2)
    assert not errors, errors


def test_poll_threads_stay_o_workers(serving, oracle):
    # the coordinator-side RPC surface must not scale with queries:
    # 2 workers -> exactly 2 reactor threads, whether 2 or 8 queries
    # are in flight (the thread-per-query polling this PR removed)
    n_workers = len(serving.workers)
    assert serving.dispatcher.poll_thread_count() == n_workers

    def poll_threads():
        return {
            t for t in threading.enumerate()
            if t.name.startswith("dispatch-poll-")
        }

    # reactors of dispatchers that earlier tests of this process
    # stopped may still be leaving their last RPC: they can only
    # exit, so they are not this dispatcher's and are not counted
    own = set(serving.dispatcher._threads.values())
    stale = poll_threads() - own
    # every statement has to reach the workers and stay there long
    # enough to be seen together: no answers from the result cache,
    # and each task holds its worker for a while
    serving.session.properties["result_cache_enabled"] = False
    serving.session.properties["fleet_task_delay_ms"] = 200
    # the serving default admits 2x the worker count at once
    max_running = 2 * n_workers
    errors = []

    def client(cid):
        try:
            serving.execute(MIX[1])
        except Exception as e:
            errors.append(f"client {cid}: {type(e).__name__}: {e}")

    counts = []
    for n_queries in (2, 8):
        threads = [
            threading.Thread(target=client, args=(c,))
            for c in range(n_queries)
        ]
        for t in threads:
            t.start()
        # sample when the statements ARE concurrent: wait for that,
        # not for a fixed time; the deadline only bounds a hang
        want = min(n_queries, max_running)
        deadline = time.monotonic() + 120
        running = len(serving.running_queries())
        while running < want and time.monotonic() < deadline:
            time.sleep(0.005)
            running = len(serving.running_queries())
        counts.append((
            n_queries,
            running,
            serving.dispatcher.poll_thread_count(),
            len(poll_threads() - stale),
        ))
        for t in threads:
            t.join()
    assert not errors, errors
    for n_queries, running, tracked, live in counts:
        assert running >= min(n_queries, max_running), (n_queries, running)
        assert tracked == n_workers, (n_queries, tracked)
        assert live == n_workers, (n_queries, live)


def test_low_weight_group_not_starved(workers, spool_root, expected):
    # weight-8 clients keep the fleet saturated; the weight-1 query
    # must still complete (DRR serves every backlogged group each
    # round) well before the heavy stream drains
    groups = ResourceGroupManager(groups=[
        ResourceGroup("heavy", user="heavy", weight=8, max_running=16),
        ResourceGroup("light", user="*", weight=1, max_running=16),
    ])
    serving = chaos_mod.make_serving(
        workers, spool_root, resource_groups=groups
    )
    try:
        stop = threading.Event()
        heavy_errors = []

        def heavy_client(cid):
            try:
                while not stop.is_set():
                    serving.execute(MIX[1], user="heavy")
            except Exception as e:
                heavy_errors.append(f"{type(e).__name__}: {e}")

        heavy = [
            threading.Thread(target=heavy_client, args=(c,))
            for c in range(4)
        ]
        for t in heavy:
            t.start()
        time.sleep(1.0)  # let the heavy stream saturate both slots
        try:
            sql = MIX[2]
            t0 = time.monotonic()
            res = serving.execute(sql, user="alice")
            light_s = time.monotonic() - t0
        finally:
            stop.set()
            for t in heavy:
                t.join(timeout=60)
        assert not heavy_errors, heavy_errors
        assert_rows_match(
            res.rows, expected[sql],
            ordered=res.ordered, abs_tol=1e-6,
        )
        # generous bound: starvation would park it behind the entire
        # unbounded heavy stream; DRR admits it within a round or two
        assert light_s < 60, f"light query starved: {light_s:.1f}s"
        st = groups.stats()
        assert st["light"]["weight"] == 1
        assert st["heavy"]["weight"] == 8
    finally:
        serving.stop()


def test_injected_failure_isolated_to_one_query(serving, expected):
    # two concurrent queries; the victim's stage-0 task-0 fails its
    # first attempt worker-side (deterministic FailureInjector analog)
    # and retries; the bystander must complete untouched — same rows,
    # zero retries
    victim_sql = MIX[1]
    bystander_sql = MIX[2]
    results = {}
    errors = []

    def run(name, sql, inject):
        try:
            results[name] = serving.execute(
                sql, inject_failures=inject
            )
        except Exception as e:
            errors.append(f"{name}: {type(e).__name__}: {e}")

    threads = [
        threading.Thread(
            target=run, args=("victim", victim_sql, {"0:0"})
        ),
        threading.Thread(
            target=run, args=("bystander", bystander_sql, None)
        ),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert results["victim"].tasks_retried >= 1
    assert results["bystander"].tasks_retried == 0
    assert_rows_match(
        results["victim"].rows, expected[victim_sql],
        ordered=results["victim"].ordered, abs_tol=1e-6,
    )
    assert_rows_match(
        results["bystander"].rows, expected[bystander_sql],
        ordered=results["bystander"].ordered, abs_tol=1e-6,
    )


def test_compiled_programs_shared_across_queries(serving, workers):
    # the worker's jit cache is process-wide: after a warmup of the
    # same statement, N concurrent repeats compile NOTHING new on any
    # worker (trino_xla_compile_total scraped before/after)
    sql = MIX[1]
    serving.execute(sql)  # warm: compile + scan residency

    def scrape(uri):
        with urllib.request.urlopen(f"{uri}/v1/metrics", timeout=5) as r:
            text = r.read().decode()
        total = 0.0
        for line in text.splitlines():
            if line.startswith("trino_xla_compile_total"):
                total += float(line.rsplit(" ", 1)[1])
        return total

    before = {u: scrape(u) for u in workers}
    errors = []

    def client(cid):
        try:
            serving.execute(sql)
        except Exception as e:
            errors.append(f"{type(e).__name__}: {e}")

    threads = [
        threading.Thread(target=client, args=(c,)) for c in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    after = {u: scrape(u) for u in workers}
    assert after == before, (before, after)


def test_http_serving_through_coordinator(workers, spool_root, expected):
    # the full stack: Coordinator(runner=ServingRunner) serving 8
    # HTTP clients; the coordinator adopts the runner's resource
    # groups and /v1/query rows carry resource_group + queued_time_ms
    from trino_tpu.server import Coordinator, StatementClient

    serving = chaos_mod.make_serving(workers, spool_root)
    coord = Coordinator(runner=serving, port=0).start()
    try:
        assert coord.resource_groups is serving.resource_groups
        errors = []

        def client(cid):
            try:
                # counts/strings only: protocol decimals arrive as
                # strings, which the oracle comparison won't coerce
                sql = MIX[1] if cid % 2 else MIX[3]
                _, rows = StatementClient(coord.uri).execute(sql)
                assert_rows_match(
                    [tuple(r) for r in rows], expected[sql],
                    ordered=True, abs_tol=1e-6,
                )
            except Exception as e:
                errors.append(f"client {cid}: {type(e).__name__}: {e}")

        threads = [
            threading.Thread(target=client, args=(c,))
            for c in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        with urllib.request.urlopen(
            f"{coord.uri}/v1/query", timeout=5
        ) as r:
            rows = json.loads(r.read())
        # the registry is process-global, so other suites' queries
        # (e.g. the starvation test's "heavy" group) may appear too —
        # assert on THIS serving runner's rows only
        mine = [
            r for r in rows if r.get("resource_group") == "global"
        ]
        assert len(mine) >= 8
        for row in mine:
            assert row.get("queued_time_ms") is not None
            assert row["queued_time_ms"] >= 0
    finally:
        coord.stop()
        serving.stop()
