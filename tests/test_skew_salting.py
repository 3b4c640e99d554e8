"""Skew-proof exchanges: salted repartition + runtime-adaptive
partition count (ROADMAP skew item (b)/(c)/(d)).

Unit tier exercises the salt filter, the SALTED/adaptive plan
invariants, and the eligibility walk; the fleet tier runs the PR 13
zipfian join against REAL worker processes and checks that

- the coordinator detects the hot probe partition off the committed
  histograms and re-plans the join stage SALTED, bringing the observed
  per-task input balance under 1.5 while the producer histogram still
  shows the hot key — with rows matching the unsalted plan and the
  sqlite oracle;
- an estimate-busting query grows the downstream exchange fabric
  (``adaptive_repartitions``), with the re-fragmented plan passing
  plan_validation=FULL;
- both re-plans survive seeded chaos (salted sub-task kill, adaptive
  growth racing task retries) oracle-exact.

Port discipline: this module owns 19090+ (test_flight_recorder.py owns
19060+, test_fleet_mesh.py 19140+).
"""

import numpy as np
import pytest

from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.exec import spool
from trino_tpu.metadata import Metadata, Session
from trino_tpu.plan import validate
from trino_tpu.plan.distribute import fragment_saltable
from trino_tpu.plan.fragment import fragment_plan, salt_stage
from trino_tpu.server.fleet import FleetRunner
from trino_tpu.testing import chaos
from trino_tpu.testing.chaos import _SKEW_SQL
from trino_tpu.testing.golden import (
    assert_rows_match,
    load_tpch_sqlite,
    to_sqlite,
)

BASE_PORT = 19090


# ---------------------------------------------------------------------------
# Unit tier: salt filter, eligibility, plan invariants
# ---------------------------------------------------------------------------


def _payload(n):
    vals = np.arange(n, dtype=np.int64)
    return {
        "names": ["k"], "types": ["bigint"],
        "cols": [(vals, None)],
    }


def test_salt_filter_partitions_rows_exactly():
    """The K salt slices of a payload are disjoint, cover every row,
    and are a pure function of (payload, salt, factor) — the property
    first-commit-wins retry correctness rests on."""
    p = _payload(103)
    slices = [spool.salt_filter(p, s, 4) for s in range(4)]
    seen = np.concatenate([sl["cols"][0][0] for sl in slices])
    assert len(seen) == 103
    assert sorted(seen.tolist()) == list(range(103))
    # deterministic: same inputs, same slice
    again = spool.salt_filter(p, 2, 4)
    assert np.array_equal(again["cols"][0][0], slices[2]["cols"][0][0])
    # validity masks ride along
    valid = np.arange(103) % 3 == 0
    pv = {
        "names": ["k"], "types": ["bigint"],
        "cols": [(np.arange(103, dtype=np.int64), valid)],
    }
    sl = spool.salt_filter(pv, 1, 4)
    v, m = sl["cols"][0]
    assert np.array_equal(m, valid[np.arange(103) % 4 == 1])
    assert np.array_equal(v, np.arange(103)[np.arange(103) % 4 == 1])


def _plan_stages(sql):
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    session = Session(catalog="tpch", schema="tiny")
    session.properties["join_distribution_type"] = "PARTITIONED"
    fleet = FleetRunner(
        ["http://127.0.0.1:1"], md, session, spool_root="/tmp/unused",
    )
    return fragment_plan(fleet._planner.plan_sql(sql))


def _join_stage(stages):
    for s in stages:
        aligned = [i for i in s.inputs if i.mode == "aligned"]
        if len(aligned) >= 2:
            return s
    raise AssertionError("no partitioned-join stage in plan")


def test_fragment_saltable_classification():
    stages = _plan_stages(_SKEW_SQL)
    join = _join_stage(stages)
    ok, reason = fragment_saltable(join.root)
    assert ok, reason
    # the fragment carrying the ORDER BY is order-sensitive, not
    # saltable
    def has_sort(n):
        import trino_tpu.plan.nodes as P
        return isinstance(n, (P.Sort, P.TopN)) or any(
            has_sort(s) for s in n.sources
        )

    sort_stage = next(s for s in stages if has_sort(s.root))
    ok, reason = fragment_saltable(sort_stage.root)
    assert not ok
    assert reason


def test_validate_rejects_bad_salt_plans():
    stages = _plan_stages(_SKEW_SQL)
    join = _join_stage(stages)
    src = next(i for i in join.inputs if i.mode == "aligned").source_id
    # a well-formed salted edge passes
    salt_stage(join, src, 4, [1])
    validate.validate_stages(stages, phase="test")
    # factor below 2 is structurally meaningless
    join.salt_plan["factor"] = 1
    with pytest.raises(validate.PlanSanityError, match="salted-exchange"):
        validate.validate_stages(stages, phase="test")
    join.salt_plan["factor"] = 4
    # the fanout source must be a declared aligned input
    join.salt_plan["source"] = "nope"
    with pytest.raises(validate.PlanSanityError, match="salted-exchange"):
        validate.validate_stages(stages, phase="test")
    join.salt_plan = None
    validate.validate_stages(stages, phase="test")
    # salt_stage itself rejects structural garbage up front
    with pytest.raises(ValueError):
        salt_stage(join, "nope", 4, [1])
    with pytest.raises(ValueError):
        salt_stage(join, src, 1, [1])
    with pytest.raises(ValueError):
        salt_stage(join, src, 4, [])


def test_validate_rejects_bad_adaptive_overrides():
    stages = _plan_stages(_SKEW_SQL)
    join = _join_stage(stages)
    # growth on a hash stage, siblings agreeing: fine
    for i in join.inputs:
        if i.mode == "aligned":
            next(
                s for s in stages if s.stage_id == i.stage_id
            ).out_partitions = 8
    validate.validate_stages(stages, phase="test")
    # disagreeing siblings feeding one consumer: rejected
    first = next(i for i in join.inputs if i.mode == "aligned")
    bad = next(s for s in stages if s.stage_id == first.stage_id)
    bad.out_partitions = 16
    with pytest.raises(
        validate.PlanSanityError, match="adaptive-repartition"
    ):
        validate.validate_stages(stages, phase="test")
    bad.out_partitions = 8
    # an override on a non-hash stage: rejected
    root = stages[-1]
    if root.partitioning != "hash":
        root.out_partitions = 8
        with pytest.raises(
            validate.PlanSanityError, match="adaptive-repartition"
        ):
            validate.validate_stages(stages, phase="test")


# ---------------------------------------------------------------------------
# Fleet tier: real workers, zipfian join
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def workers():
    procs, uris = chaos.spawn_workers(2, base_port=BASE_PORT, platform="cpu")
    yield uris
    chaos.stop_workers(procs)


@pytest.fixture(scope="module")
def oracle():
    data = QueryRunner.tpch("tiny").metadata.connector("tpch").data("tiny")
    return load_tpch_sqlite(data)


@pytest.fixture()
def make_fleet(workers, tmp_path):
    def _make(**props):
        md = Metadata()
        md.register_catalog("tpch", TpchConnector())
        session = Session(catalog="tpch", schema="tiny")
        session.properties["join_distribution_type"] = "PARTITIONED"
        session.properties["plan_validation"] = "FULL"
        session.properties.update(props)
        return FleetRunner(
            workers, md, session,
            spool_root=str(tmp_path / "spool"), n_partitions=4,
        )
    return _make


def _run_checked(fleet, oracle, sql):
    res = fleet.execute(sql)
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(res.rows, expected, ordered=res.ordered,
                      abs_tol=1e-6)
    return res


def _salted_stage_stats(res):
    return [st for st in res.stage_stats if st.get("salted")]


def test_salted_replan_beats_skew(make_fleet, oracle):
    # baseline: unsalted plan sees the hot probe partition
    base = _run_checked(make_fleet(), oracle, _SKEW_SQL)
    assert base.salted_edges == 0
    probe = max(
        float((st.get("partition_skew") or {}).get("max_mean_ratio", 0))
        for st in base.stage_stats
        if st["rows_out"] >= 1000
        and int((st.get("partition_skew") or {}).get("partitions", 0)) > 1
    )
    assert probe >= 2.0

    # factor 8: each hot salt task reads hot/8 fanout rows plus one
    # whole replicate partition, landing well under the 1.5 balance
    # target (factor 4 floors at ~1.53 on this shape — the salt tasks
    # themselves become the evenly-sized maximum)
    salted = _run_checked(
        make_fleet(
            skew_salt_threshold=2.0, skew_salt_factor=8,
            check_exchange_coverage=True,
        ),
        oracle, _SKEW_SQL,
    )
    assert salted.salted_edges >= 1
    # identical rows either way (both already oracle-checked)
    assert_rows_match(
        salted.rows, base.rows, ordered=salted.ordered, abs_tol=1e-6
    )
    [st] = _salted_stage_stats(salted)
    assert st["salted"]["factor"] == 8
    assert st["salted"]["hot"], st["salted"]
    # the K salt tasks split the hot partition's rows: per-task input
    # balance lands under 1.5 even though the PRODUCER histogram (which
    # read-side salting never rewrites) still flags the hot key
    assert st["input_skew"]["max_mean_ratio"] < 1.5, st["input_skew"]
    producer_ratios = [
        float((x.get("partition_skew") or {}).get("max_mean_ratio", 0))
        for x in salted.stage_stats if x["rows_out"] >= 1000
    ]
    assert max(producer_ratios) >= 2.0
    # more tasks than partitions: the hot partition fanned out
    assert st["tasks"] > 4


def test_salted_rendered_in_explain_analyze(make_fleet, oracle):
    fleet = make_fleet(skew_salt_threshold=2.0, skew_salt_factor=4)
    res = fleet.execute("EXPLAIN ANALYZE " + _SKEW_SQL)
    text = "\n".join(r[0] for r in res.rows)
    assert "salted ×4" in text, text
    assert "hot partition" in text, text


def test_adaptive_growth_refragments_downstream(make_fleet, oracle):
    # a deliberately low trigger stands in for an estimate-busting
    # query: the join stage's committed input rows exceed factor x the
    # CBO estimate, so its OUTPUT fabric grows 4 -> 8 before admission
    res = _run_checked(
        make_fleet(
            adaptive_partition_growth_factor=0.5,
            adaptive_partition_max=8,
        ),
        oracle, _SKEW_SQL,
    )
    assert res.adaptive_repartitions >= 1
    grown = [st for st in res.stage_stats if st.get("out_partitions")]
    assert grown and all(
        st["out_partitions"] == 8 for st in grown
    ), grown
    # the grown stage's consumer runs one task per NEW partition
    consumers = [st for st in res.stage_stats if st["tasks"] == 8]
    assert consumers, [
        (st["stage_id"], st["tasks"]) for st in res.stage_stats
    ]
    analyze = make_fleet(
        adaptive_partition_growth_factor=0.5, adaptive_partition_max=8,
    ).execute("EXPLAIN ANALYZE " + _SKEW_SQL)
    atext = "\n".join(r[0] for r in analyze.rows)
    assert "(adaptive)" in atext, atext


def test_static_plan_untouched_when_disabled(make_fleet, oracle):
    res = _run_checked(make_fleet(), oracle, _SKEW_SQL)
    assert res.salted_edges == 0
    assert res.adaptive_repartitions == 0
    assert all(
        st["tasks"] <= 4 and not st.get("salted")
        for st in res.stage_stats
    )


def test_skew_chaos_scenarios(workers, tmp_path, oracle):
    record = chaos.run_skew_chaos(
        workers, str(tmp_path / "spool"), seed=7, oracle=oracle
    )
    names = [r["scenario"] for r in record["runs"]]
    assert names == ["salted-kill", "adaptive-race"]
    assert record["runs"][0]["tasks_retried"] >= 1
    assert record["runs"][1]["adaptive_repartitions"] >= 1
