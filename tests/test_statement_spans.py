"""One span tree per statement (ISSUE 25): the tree a served statement
yields, its nesting, the runner-lock wait, the flat totals on ``GET
/v1/query``, the tree on ``GET /v1/query/{id}``, the protocol's stats,
``build_trace`` on a jit miss, every device->host read inside a
``host_sync`` or ``to_rows`` span, program names, and no backend
initialised by opening spans."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time
import urllib.request

import jax
import pytest

from trino_tpu import telemetry
from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.engine import QueryRunner
from trino_tpu.server.coordinator import Coordinator

#: the names of a served statement's tree (PERF.md section 3 lists them)
ALWAYS = {"statement", "queued", "runner_wait", "parse", "plan",
          "execute", "to_rows", "epilogue", "respond"}
UNDER_EXECUTE = {"dispatch", "build_trace", "host_sync", "upload"}
#: one a recorder of the epilogue that costs anything
UNDER_EPILOGUE = {"operator_stats", "compile_snapshot", "plan_digest",
                  "time_breakdown", "listeners", "slow_query"}
FLAT_FIELDS = (
    "runner_wait_ms", "parse_ms", "plan_ms", "execute_ms",
    "build_trace_ms", "host_sync_ms", "host_syncs", "dispatches",
    "upload_ms", "to_rows_ms", "respond_ms", "rows_out_ms",
    "direct_groupbys", "sorted_groupbys", "streamed_groupbys",
    "groupby_start_walks", "compactions", "compact_gather_ops",
    "small_build_joins", "sorted_joins", "narrow_key_joins",
    "wide_key_joins", "outer_joins", "anti_joins", "distinct_aggregates",
    "revoked_joins", "join_revoked_ms",
)
PROGRAM = re.compile(
    r"^(chain_[A-Za-z_]+|join_count|join_bounds|join_expand|semi_join"
    r"|compact)$"
)
FOUR = ("q01", "q03", "q06", "q18")


@pytest.fixture(scope="module")
def coord():
    c = Coordinator(runner=QueryRunner.tpch("tiny"), port=0).start()
    yield c
    c.stop()


def get(coord, path):
    with urllib.request.urlopen(coord.uri + path, timeout=30) as r:
        return json.loads(r.read())


def joins_counted(search=None, key_bits=None):
    """``trino_joins_total`` summed over the series of one search, one
    width, or all."""
    return sum(
        telemetry.JOINS.value(search=s, key_bits=str(b))
        for s in ("count", "sort") if search in (None, s)
        for b in range(1, 65) if key_bits in (None, b)
    )


def serve(coord, sql):
    """Run one statement through the protocol; the first response's id."""
    req = urllib.request.Request(
        coord.uri + "/v1/statement", data=sql.encode(), method="POST"
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        first = json.loads(r.read())
    resp, last = first, first
    while resp.get("nextUri"):
        with urllib.request.urlopen(resp["nextUri"], timeout=60) as r:
            resp = last = json.loads(r.read())
    assert "error" not in last, last
    return first["id"], last


def row_of(coord, qid):
    """The statement's row of ``GET /v1/query`` once its ``respond``
    span is on it (the handler closes the span after the client has the
    last byte, so a client can ask a moment too soon)."""
    deadline = time.time() + 5
    while True:
        row = {q["query_id"]: q for q in get(coord, "/v1/query")}[qid]
        if row.get("respond_ms") or time.time() > deadline:
            return row
        time.sleep(0.01)


def walk(span, parent=None):
    yield span, parent
    for ch in span["children"]:
        yield from walk(ch, span)


def end_ms(span):
    return span["start_ms"] + span["duration_ms"]


def test_served_statement_yields_one_tree_of_documented_names(coord):
    qid, _ = serve(coord, QUERIES["q03"])
    row_of(coord, qid)
    tree = get(coord, f"/v1/query/{qid}")["spans"]
    spans = list(walk(tree))
    names = {sp["name"] for sp, _ in spans}
    assert ALWAYS <= names <= ALWAYS | UNDER_EXECUTE | UNDER_EPILOGUE, names
    assert tree["name"] == "statement" and tree["parent_id"] is None
    ids = {sp["span_id"] for sp, _ in spans}
    assert len(ids) == len(spans)
    for sp, parent in spans:
        assert sp["query_id"] == qid, sp
        if parent is not None:
            assert sp["parent_id"] == parent["span_id"] and \
                sp["parent_id"] in ids
    top = [sp["name"] for sp in tree["children"]]
    assert top == ["queued", "runner_wait", "parse", "plan", "execute",
                   "to_rows", "epilogue", "respond"], top
    under = {sp["name"] for sp, par in spans
             if par is not None and par["name"] == "execute"}
    assert under <= UNDER_EXECUTE and "dispatch" in under
    for sp, par in spans:
        if sp["name"] == "build_trace":
            assert par["name"] == "dispatch"


def test_epilogue_is_on_the_tree_and_on_the_row(coord):
    """What the runner still does under its lock after ``to_rows`` is a
    span of its own (``device.idle_in_epilogue_share`` reads it), one
    child a recorder, and ``_seal`` puts its total on the row."""
    qid, _ = serve(coord, QUERIES["q06"])
    row = row_of(coord, qid)
    tree = get(coord, f"/v1/query/{qid}")["spans"]
    (epilogue,) = [sp for sp in tree["children"] if sp["name"] == "epilogue"]
    kids = [sp["name"] for sp in epilogue["children"]]
    assert set(kids) <= UNDER_EPILOGUE and "time_breakdown" in kids
    assert row["epilogue_ms"] == pytest.approx(
        epilogue["duration_ms"], abs=0.01)
    assert sum(sp["duration_ms"] for sp in epilogue["children"]) <= (
        epilogue["duration_ms"] + 0.5)
    # it starts where to_rows ends: nothing between them is unspanned
    (to_rows,) = [sp for sp in tree["children"] if sp["name"] == "to_rows"]
    assert epilogue["start_ms"] - end_ms(to_rows) < 5.0
    # the flight recorder's window is the statement's execution still
    res = QueryRunner.tpch("tiny").execute("select count(*) from nation")
    assert "epilogue" in {sp.name for sp in res.trace.root.children}
    assert res.time_breakdown is not None


def test_children_lie_inside_their_parents_and_add_up(coord):
    qid, _ = serve(coord, QUERIES["q18"])
    row_of(coord, qid)
    tree = get(coord, f"/v1/query/{qid}")["spans"]
    slack = 0.5  # ms: start is the wall clock, duration the monotonic
    for sp, parent in walk(tree):
        if parent is None or sp["name"] == "respond":
            continue  # respond runs after ``statement`` closed
        assert sp["start_ms"] >= parent["start_ms"] - slack, sp["name"]
        assert end_ms(sp) <= end_ms(parent) + slack, sp["name"]
    parts = sum(sp["duration_ms"] for sp in tree["children"]
                if sp["name"] != "respond")
    assert tree["duration_ms"] >= parts - slack
    # nothing is timed twice: the layers of a statement, in order
    kids = [sp for sp in tree["children"] if sp["name"] != "respond"]
    for a, b in zip(kids, kids[1:]):
        assert end_ms(a) <= b["start_ms"] + slack, (a["name"], b["name"])
    respond = [sp for sp in tree["children"] if sp["name"] == "respond"]
    assert respond and respond[0]["start_ms"] >= end_ms(tree) - slack


def test_runner_wait_is_the_wait_for_the_statement_ahead():
    runner = QueryRunner.tpch("tiny")
    coord = Coordinator(runner=runner, port=0).start()
    try:
        serve(coord, "select count(*) from nation")  # warm
        lone, _ = serve(coord, "select count(*) from nation")
        row = {q["query_id"]: q for q in get(coord, "/v1/query")}[lone]
        assert row["runner_wait_ms"] < 5.0, row
        # two statements together against one runner: the second waits
        # on the runner's lock for as long as the first executes
        runner.session.properties["execution_delay_ms"] = 400.0
        first = coord.submit("select count(*) from region")
        deadline = time.time() + 10
        while first.state != "RUNNING" and time.time() < deadline:
            time.sleep(0.005)
        second = coord.submit("select count(*) from region")
        for q in (first, second):
            while q.state in ("QUEUED", "RUNNING") and time.time() < deadline:
                time.sleep(0.01)
            assert q.state == "FINISHED", (q.state, q.error)
        rows = {q["query_id"]: q for q in get(coord, "/v1/query")}
        a, b = rows[first.query_id], rows[second.query_id]
        assert a["runner_wait_ms"] < 5.0, a
        # the first holds the lock for its sleep and its execution; the
        # second came within milliseconds of the first's start
        assert b["runner_wait_ms"] >= 400.0 - 100.0, (a, b)
        assert b["runner_wait_ms"] >= a["execute_ms"] - 100.0, (a, b)
        # both were RUNNING for the resource group all the while
        assert b["queued_time_ms"] < 100.0, b
    finally:
        runner.session.properties.pop("execution_delay_ms", None)
        coord.stop()


def test_query_rows_carry_flat_fields_and_the_id_serves_the_tree(coord):
    qid, _ = serve(coord, QUERIES["q06"])
    row = row_of(coord, qid)
    for f in FLAT_FIELDS + ("queued_time_ms",):
        assert isinstance(row[f], (int, float)) and not isinstance(
            row[f], bool), (f, row.get(f))
        assert row[f] >= 0
    assert row["plan_ms"] > 0 and row["execute_ms"] > 0
    assert row["dispatches"] >= 1
    assert row["rows_out_ms"] == pytest.approx(
        row["to_rows_ms"] + row["respond_ms"])
    info = get(coord, f"/v1/query/{qid}")
    tree = info["spans"]
    by_name: dict = {}
    for sp, _ in walk(tree):
        by_name[sp["name"]] = by_name.get(sp["name"], 0.0) + sp["duration_ms"]
    for name in ("runner_wait", "parse", "plan", "execute", "to_rows",
                 "respond"):
        assert row[name + "_ms"] == pytest.approx(by_name[name]), name
    assert info["plan_ms"] == row["plan_ms"]


@pytest.mark.parametrize("q,direct,by_sort,streamed", [
    ("q01", 1, 0, 0), ("q03", 0, 1, 0), ("q06", 0, 0, 0), ("q18", 0, 1, 1),
])
def test_query_rows_count_grouped_aggregates_by_their_path(
        coord, q, direct, by_sort, streamed):
    """A key domain of a few bits is addressed by slot (ISSUE 26), a
    whole table scanned in its declared key order is grouped by its
    runs (Q18's inner ``group by l_orderkey``, ISSUE 31), any other
    sorted; a warm dispatch reports its program's paths too, so the
    second run of a statement reads the same."""
    for _ in range(2):
        qid, _ = serve(coord, QUERIES[q])
        row = row_of(coord, qid)
        assert (row["direct_groupbys"], row["sorted_groupbys"],
                row["streamed_groupbys"]) == (direct, by_sort, streamed), row


@pytest.mark.parametrize("q", FOUR)
def test_query_rows_count_the_walks_at_their_groups_first_rows(coord, q):
    """A grouped aggregate reads its keys and its integer sums at its
    groups' first rows in stacked walks of 32-bit words (ISSUE 44): the
    ``dispatch`` span of a chain says in how many ``[capacity]``-sized
    gathers (``kernels.gather_plan`` over the columns read there), the
    row sums them; a warm dispatch reports the same. Q18's inner step
    reads two int64 limb sums and its int64 key: six words, two
    gathers, where three lone int64 gathers stood."""
    from trino_tpu.exec import kernels as K

    for _ in range(2):
        qid, _ = serve(coord, QUERIES[q])
        row = row_of(coord, qid)
        chains = [
            sp["attrs"] for sp, _ in
            walk(get(coord, f"/v1/query/{qid}")["spans"])
            if sp["name"] == "dispatch" and "groupbys" in sp["attrs"]
        ]
        assert row["groupby_start_walks"] == sum(
            a["start_walks"] for a in chains)
        if q == "q06":  # an ungrouped aggregate reads at no group's row
            assert row["groupby_start_walks"] == 0
            continue
        assert all(a["start_walks"] >= len(a["groupbys"]) for a in chains)
        if q == "q18":
            (inner,) = [a for a in chains if a["groupbys"] == ["streamed"]]
            i64 = (jax.numpy.int64, (), False)
            assert inner["start_walks"] == K.gather_plan([i64] * 3)[1] == 2


@pytest.mark.parametrize("q,compacts", [
    ("q01", False), ("q03", True), ("q06", False), ("q18", True),
])
def test_query_rows_count_compactions_and_their_gather_operands(
        coord, q, compacts):
    """A compaction reads its page at the sorted positions in stacked
    gathers (``kernels.gather_rows``, ISSUE 36): the row says how many
    compaction programs the statement dispatched and how many gather
    operands they hold together — fewer than the columns they moved;
    a warm dispatch reports the same."""
    for _ in range(2):
        qid, _ = serve(coord, QUERIES[q])
        row = row_of(coord, qid)
        spans = [
            sp for sp, _ in walk(get(coord, f"/v1/query/{qid}")["spans"])
            if sp["name"] == "dispatch"
            and sp["attrs"]["program"] == "compact"
        ]
        assert row["compactions"] == len(spans)
        assert row["compact_gather_ops"] == sum(
            sp["attrs"]["gather_ops"] for sp in spans)
        for sp in spans:
            assert sp["attrs"]["rows_out"] <= sp["attrs"]["rows_in"]
        if not compacts:
            assert (row["compactions"], row["compact_gather_ops"]) == (0, 0)
            continue
        assert row["compactions"] >= 1
        assert row["compactions"] <= row["compact_gather_ops"] < sum(
            sp["attrs"]["columns"] for sp in spans)


#: a join built over the whole of ``orders`` (16,384 rows at ``tiny``)
BIG_BUILD_JOIN = ("select count(*) from lineitem, orders"
                  " where l_orderkey = o_orderkey")


@pytest.mark.parametrize("sql,small,by_sort", [
    (QUERIES["q01"], 0, 0), (QUERIES["q06"], 0, 0),
    # Q18: its semi join and its ``lineitem`` join are built over the
    # few orders that pass the HAVING; its ``customer`` join over
    # ``customer``, which at ``tiny`` is 1,536 rows and counts too (at
    # SF1 it is 196,608 and sorts: the row reads 2 and 1 there)
    (QUERIES["q18"], 3, 0), (BIG_BUILD_JOIN, 0, 1),
])
def test_query_rows_count_joins_by_their_search(coord, sql, small, by_sort):
    """A join whose build side is a handful of rows ranks its probe by
    compare-and-count, any other by sort (``kernels.join_search``,
    ISSUE 42): the ``dispatch`` span of a program that holds a
    ``join_ranges`` says which, the row sums them, and
    ``trino_joins_total`` moves by the same counts; a warm dispatch
    reports the same."""
    from trino_tpu.exec import kernels as K

    for _ in range(2):
        before = {s: joins_counted(s) for s in ("count", "sort")}
        qid, _ = serve(coord, sql)
        row = row_of(coord, qid)
        assert (row["small_build_joins"], row["sorted_joins"]) == (
            small, by_sort), row
        for search, n in (("count", small), ("sort", by_sort)):
            assert joins_counted(search) - before[search] == n
        noted = [
            sp["attrs"] for sp, _ in walk(get(coord, f"/v1/query/{qid}")["spans"])
            if sp["name"] == "dispatch" and "join_search" in sp["attrs"]
        ]
        assert len(noted) == small + by_sort
        for attrs in noted:
            assert attrs["program"] in ("join_count", "semi_join")
            assert attrs["join_search"] == K.join_search(attrs["build_rows"])
            assert (attrs["join_search"] == "count") == (
                attrs["build_rows"] <= K.JOIN_SMALL_BUILD)
    text = urllib.request.urlopen(coord.uri + "/v1/metrics").read().decode()
    series = r'trino_joins_total\{key_bits="\d+",search="%s"\}'
    assert re.search(series % "sort", text) or not by_sort
    assert re.search(series % "count", text) or not small


@pytest.mark.parametrize("sql,narrow,widths", [
    (QUERIES["q01"], 0, []), (QUERIES["q06"], 0, []),
    # Q3 at ``tiny``: ``o_custkey`` / ``c_custkey`` lie in [1, 1500]
    # (11 bits), ``l_orderkey`` / ``o_orderkey`` in [1, 59976] (16)
    (QUERIES["q03"], 2, [11, 16]),
    # Q18: the same two joins, and its semi join too — the subquery's
    # ``l_orderkey`` keeps its exact bounds through the Aggregate (a
    # group key's stats are its source's) and the HAVING (a filter on
    # the sum narrows nothing of the key)
    (QUERIES["q18"], 3, [11, 16, 16]),
    # two columns are one hashed key: 64 bits, whatever their ranges
    ("select count(*) from lineitem, partsupp"
     " where l_partkey = ps_partkey and l_suppkey = ps_suppkey", 0, [64]),
])
def test_query_rows_count_joins_ranked_at_their_keys_width(
        coord, sql, narrow, widths):
    """A join on one integer key whose exact range the plan proves
    (``Join.key_ranges``, ISSUE 46) ranks it at ``bit_length(hi - lo)``
    bits: the ``dispatch`` span of the program that holds the
    ``join_ranges`` carries ``key_bits``, the row counts those below 64
    as ``narrow_key_joins``, and ``trino_joins_total`` takes the width
    as a label; a warm dispatch reports the same."""
    for _ in range(2):
        before = {b: joins_counted(key_bits=b) for b in set(widths)}
        qid, _ = serve(coord, sql)
        row = row_of(coord, qid)
        assert row["narrow_key_joins"] == narrow, row
        noted = sorted(
            sp["attrs"]["key_bits"]
            for sp, _ in walk(get(coord, f"/v1/query/{qid}")["spans"])
            if sp["name"] == "dispatch" and "join_search" in sp["attrs"]
        )
        assert noted == widths
        assert narrow == sum(b < 64 for b in widths)
        for b in set(widths):
            assert joins_counted(key_bits=b) - before[b] == widths.count(b)


#: the row fields of ISSUE 48, each with the series that counts it
KIND_FIELDS = {
    "wide_key_joins": telemetry.WIDE_KEY_JOINS,
    "outer_joins": telemetry.OUTER_JOINS,
    "anti_joins": telemetry.ANTI_JOINS,
    "distinct_aggregates": telemetry.DISTINCT_AGGREGATES,
    "revoked_joins": telemetry.JOIN_REVOCATIONS,
}


@pytest.mark.parametrize("q,joins,want", [
    # five joins, the fifth on (ps_partkey, ps_suppkey): one hashed key
    ("q09", 5, {"wide_key_joins": 1}),
    # the decorrelated average joins back as a LEFT join
    ("q17", 2, {"outer_joins": 1}),
    ("q13", 1, {"outer_joins": 1}),
    # NOT IN: a semi join under a Filter that negates its match
    ("q16", 2, {"anti_joins": 1, "distinct_aggregates": 1}),
    # IN: a semi join, not an anti join
    ("q18", 3, {}),
    ("q03", 2, {}),
])
def test_query_rows_count_joins_by_kind_and_distinct_aggregates(
        coord, q, joins, want):
    """What kind of join a statement ran, whether its keys were ranked
    at 64 bits and whether it evaluated a DISTINCT aggregate are on its
    row (``wide_key_joins``, ``outer_joins``, ``anti_joins``,
    ``distinct_aggregates``; 0 where it ran none), on the ``dispatch``
    span of the program (``join_kind``, ``key_bits``,
    ``distinct_aggregates``) and in a series each; a warm dispatch
    reports the same."""
    for _ in range(2):
        before = {f: c.total() for f, c in KIND_FIELDS.items()}
        qid, _ = serve(coord, QUERIES[q])
        row = row_of(coord, qid)
        assert row["small_build_joins"] + row["sorted_joins"] == joins, row
        assert row["narrow_key_joins"] + row["wide_key_joins"] == joins, row
        for f, counter in KIND_FIELDS.items():
            assert row[f] == want.get(f, 0), (f, row)
            assert counter.total() - before[f] == want.get(f, 0), f
        kinds = sorted(
            sp["attrs"]["join_kind"]
            for sp, _ in walk(get(coord, f"/v1/query/{qid}")["spans"])
            if sp["name"] == "dispatch" and "join_search" in sp["attrs"]
        )
        assert len(kinds) == joins
        assert sum(k in ("left", "right", "full") for k in kinds) == want.get(
            "outer_joins", 0)
        assert kinds.count("anti") == want.get("anti_joins", 0)


def test_a_revoked_join_shows_as_a_span_a_row_field_and_a_series(monkeypatch):
    """A join whose estimated working set passes
    ``query_max_memory_per_node`` runs through the spill tier
    (``LocalExecutor._maybe_revoke_join``): the statement's tree holds a
    ``join-revoked`` span with the estimate, the cap and ``forced``, its
    row counts it as ``revoked_joins`` with the span's time beside it,
    ``trino_join_revocations_total`` moves, and the answer is the
    resident run's. (The cap and the statement are
    tests/test_memory_governance.py's revocation case.)"""
    from trino_tpu.exec import spill

    monkeypatch.setattr(spill, "MIN_CHUNK_ROWS", 8192)
    sql = ("select count(*) from lineitem l1, lineitem l2 "
           "where l1.l_orderkey = l2.l_orderkey "
           "and l1.l_linenumber = l2.l_linenumber")
    cap = 2 << 20
    runner = QueryRunner.tpch("tiny")
    runner.session.properties["query_max_memory_per_node"] = str(cap)
    c = Coordinator(runner=runner, port=0).start()
    try:
        before = telemetry.JOIN_REVOCATIONS.total()
        qid, last = serve(c, sql)
        row = row_of(c, qid)
        spans = [sp for sp, _ in walk(get(c, f"/v1/query/{qid}")["spans"])
                 if sp["name"] == "join-revoked"]
    finally:
        c.stop()
    assert last["data"] == [list(r) for r in
                            QueryRunner.tpch("tiny").execute(sql).rows]
    assert row["revoked_joins"] == len(spans) >= 1, row
    assert row["join_revoked_ms"] > 0
    assert telemetry.JOIN_REVOCATIONS.total() - before == len(spans)
    for sp in spans:
        assert sp["attrs"]["cap_bytes"] == cap
        assert sp["attrs"]["estimated_bytes"] > 0
        assert sp["attrs"]["forced"] in (True, False)


def test_protocol_stats_carry_queued_and_planning_time(coord):
    coord.runner.session.properties["planning_delay_ms"] = 30.0
    try:
        _, last = serve(coord, "select count(*) from supplier")
    finally:
        coord.runner.session.properties.pop("planning_delay_ms", None)
    stats = last["stats"]
    assert stats["state"] == "FINISHED"
    assert isinstance(stats["queuedTimeMillis"], int)
    assert isinstance(stats["planningTimeMillis"], int)
    assert stats["planningTimeMillis"] >= 30
    assert stats["queuedTimeMillis"] <= stats["elapsedTimeMillis"]


def test_a_jit_miss_has_a_build_trace_child_and_a_hit_none():
    runner = QueryRunner.tpch("tiny")
    sql = "select sum(s_acctbal) from supplier where s_suppkey < 37"
    cold = runner.execute(sql).trace
    warm = runner.execute(sql).trace
    cold_d = cold.find(name="dispatch")
    assert cold_d and all(d.attrs["miss"] for d in cold_d)
    for d in cold_d:
        assert [c.name for c in d.children] == ["build_trace"]
        assert d.children[0].duration_ms <= d.duration_ms
    warm_d = warm.find(name="dispatch")
    assert len(warm_d) == len(cold_d)
    assert not any(d.attrs["miss"] or d.children for d in warm_d)
    assert not warm.find(name="build_trace")
    # planning_ms is the plan span's duration (no hand-kept twin)
    res = runner.execute(sql)
    assert res.planning_ms == pytest.approx(
        sum(s.duration_ms for s in res.trace.find(name="plan")))
    assert res.trace.find(kind="planning")[0].name == "plan"
    assert not hasattr(runner, "_plan_ms")


@pytest.mark.parametrize("q", FOUR)
def test_every_device_read_falls_inside_a_host_sync_or_to_rows(q, monkeypatch):
    """The transfer guard is silent on the CPU backend, so this is the
    CPU-side check: ``jax.device_get`` wrapped, every call made while
    the statement runs must find a ``host_sync`` span open on its
    thread (or be the result transfer inside ``to_rows``)."""
    runner = QueryRunner.tpch("tiny")
    runner.execute(QUERIES[q])  # learned capacities, programs built
    real = jax.device_get
    seen: list = []

    def wrapped(x):
        active = telemetry.active_span()
        seen.append(active.name if active is not None else None)
        return real(x)

    monkeypatch.setattr(jax, "device_get", wrapped)
    res = runner.execute(QUERIES[q])
    monkeypatch.undo()
    trace = res.trace
    # ``to_rows`` is opened by the engine with no thread anchor: the one
    # read with no active span is the result transfer inside it
    outside = [s for s in seen if s != "host_sync"]
    assert outside == [None], seen
    assert len(trace.find(name="to_rows")) == 1
    syncs = [s for s in trace.find(name="host_sync")]
    # one more than the reads: the wait for the result page's programs
    # (no transfer), the last thing under ``execute``
    assert len(syncs) == seen.count("host_sync") + 1
    assert [s.attrs["site"] for s in syncs].count("result") == 1
    execute = trace.find(name="execute")[0]
    assert execute.children[-1].attrs.get("site") == "result"
    assert all(s in list(execute.walk()) for s in syncs)
    assert all(s.attrs.get("site") for s in syncs)
    totals = telemetry.span_totals(trace.root)
    assert totals.get("host_syncs", 0) == len(syncs)


def test_the_flight_recorder_reads_the_executors_spans_as_execution():
    """``time_breakdown`` (EXPLAIN ANALYZE's footer, history's buckets,
    the sentry's attribution) read ``execute``'s self time as
    scan/compute before the executor's work was split into spans; the
    spans under it and ``to_rows`` beside it are execution time too."""
    from trino_tpu import telemetry_analysis

    runner = QueryRunner.tpch("tiny")
    runner.execute(QUERIES["q01"])
    res = runner.execute(QUERIES["q01"])
    execute = res.trace.find(name="execute")[0]
    under = [s for s in execute.walk() if s is not execute]
    assert under and {s.kind for s in under} == {"execution"}
    assert res.trace.find(name="to_rows")[0].kind == "execution"
    for sp in under:
        assert telemetry_analysis._classify(sp) == telemetry_analysis._EXEC
    b = res.time_breakdown["buckets"]
    exec_ms = execute.duration_ms + res.trace.find(name="to_rows")[0].duration_ms
    # what the statement spent executing is in scan + compute, not other
    held = b["scan"] + b["compute"] + b["xla_compile"]
    assert 0.9 * exec_ms <= held <= 1.01 * exec_ms, (b, exec_ms)
    assert b["other"] < 0.5 * held, b


def test_a_worker_tasks_executor_spans_keep_the_tasks_kind():
    task = telemetry.Span(name="task t0", kind="task")
    telemetry.set_active_span(task)
    try:
        with telemetry.child_span("dispatch", program="compact") as d:
            with telemetry.child_span("build_trace") as b:
                pass
    finally:
        telemetry.set_active_span(None)
    assert d.kind == b.kind == "task" and d.attrs == {"program": "compact"}
    assert telemetry.active_span() is None


def test_a_page_fetched_again_adds_no_respond_span(coord):
    qid, _ = serve(coord, "select n_name from nation order by 1")
    row = row_of(coord, qid)
    q = coord._queries[qid]
    n = sum(1 for c in q.tracer.root.children if c.name == "respond")
    assert n >= 1 and q.responded
    again = urllib.request.urlopen(
        f"{coord.uri}/v1/statement/executing/{qid}/{q.slug}/0", timeout=30
    )
    assert json.loads(again.read())["data"]
    after = row_of(coord, qid)
    assert sum(1 for c in q.tracer.root.children
               if c.name == "respond") == n
    assert after["respond_ms"] == row["respond_ms"]
    # the planning total is kept at the seal, not walked out each poll
    assert q.planning_ms == pytest.approx(after["plan_ms"])


def test_every_program_of_the_four_queries_is_named_by_what_it_does():
    runner = QueryRunner.tpch("tiny")
    programs = set()
    for q in FOUR:
        trace = runner.execute(QUERIES[q]).trace
        dispatches = trace.find(name="dispatch")
        assert dispatches, q
        programs |= {d.attrs["program"] for d in dispatches}
    bad = sorted(p for p in programs if not PROGRAM.match(p))
    assert not bad, bad
    assert any(p.startswith("chain_") for p in programs)
    assert {"join_count", "join_expand", "semi_join", "compact"} <= programs
    # the name reaches XLA: a jitted program's module is jit_<name>
    from trino_tpu.exec.local import _named_jit

    def counted(x):
        return x + 1

    lowered = _named_jit(counted, "chain_Filter_Aggregate").lower(1.0)
    assert "module @jit_chain_Filter_Aggregate" in lowered.as_text()


def test_opening_spans_initialises_no_backend():
    # a fresh interpreter, as the host-only fleet coordinator is one
    code = (
        "import trino_tpu\n"
        "from trino_tpu import telemetry\n"
        "from jax._src import xla_bridge\n"
        "tr = telemetry.Tracer('q1', root_name='statement')\n"
        "with tr.span('plan', 'planning'):\n"
        "    telemetry.set_active_span(tr.root)\n"
        "    with telemetry.child_span('host_sync', site='t') as sp:\n"
        "        assert sp.query_id == 'q1'\n"
        "trace = tr.finish()\n"
        "assert [s.name for s in trace.spans()] == "
        "['statement', 'plan', 'host_sync'], trace.spans()\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-2000:]


def test_spans_are_events_on_the_profilers_host_plane(tmp_path):
    """One clock, two views: while a profiler session runs, a statement's
    spans are annotations of the trace, each with the query id."""
    import glob

    import jax.profiler as jp
    from jax.profiler import ProfileData

    runner = QueryRunner.tpch("tiny")
    sql = "select count(*) from nation where n_regionkey = 1"
    runner.execute(sql)
    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jp.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = runner.execute(sql, query_id="q_traced")
    finally:
        jp.stop_trace()
    path = sorted(glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if stats.get("query_id") == "q_traced":
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    want = {s.name for s in res.trace.spans()}
    assert want <= set(events), (want, set(events))
    # the spans nest in the trace as they do in the tree
    (s_lo, s_hi), = events["statement"]
    for name in ("parse", "plan", "execute", "to_rows"):
        for lo, hi in events[name]:
            assert s_lo <= lo and hi <= s_hi, name
    (e_lo, e_hi), = events["execute"]
    for lo, hi in events["dispatch"]:
        assert e_lo <= lo and hi <= e_hi
