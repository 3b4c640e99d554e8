"""The arithmetic of the end-to-end metrics on hand-made latency lists:
``query_geomean_ms`` is a template's typical latency and sets one late
statement aside, ``queries_per_s`` pays for it, a failed statement makes
the run not correct, and ``protocol.late_stmt_share`` counts what was
set aside."""

import math
from types import SimpleNamespace

import e2e
import run as harness


def test_query_geomean_is_geomean_of_template_medians():
    lat = {"a": [100.0, 300.0, 110.0], "b": [50.0], "c": [400.0, 420.0]}
    # medians 110, 50, 410 -> (110*50*410) ** (1/3)
    assert math.isclose(e2e.query_geomean_ms(lat), (110 * 50 * 410) ** (1 / 3))
    # a level that moves, moves it: every execution of one template 10 % up
    up = dict(lat, c=[440.0, 462.0])
    assert math.isclose(e2e.query_geomean_ms(up) / e2e.query_geomean_ms(lat),
                        1.1 ** (1 / 3))
    assert e2e.query_geomean_ms({}) is None


def window(latencies_ms):
    """Statements of a closed loop sent back to back from t = 100 s."""
    sts, t = [], 100.0
    for template, ms in latencies_ms:
        sts.append(SimpleNamespace(template=template, cls="long", sent_s=t,
                                   done_s=t + ms / 1e3, due_s=0.0,
                                   error=None, correct=True))
        t += ms / 1e3
    return sts


def numbers(sts):
    ctx = harness.Context()
    ctx.statements, ctx.t0 = sts, 100.0
    bench = {"end_to_end": [{"name": n, "unit": "u"} for n in (
        "query_geomean_ms", "queries_per_s", "setup_s")]}
    got = harness.end_to_end(bench, "any", ctx, setup_s=1.0)
    late = harness.load_reader("client_clock")(ctx, "late_stmt_share")
    return (got["query_geomean_ms"]["value"], got["queries_per_s"]["value"],
            late)


def test_one_stalled_execution_in_thirty_leaves_the_geomean_and_lowers_the_rate():
    calm = [(t, ms) for _ in range(30) for t, ms in (("q01", 18.0), ("q18", 965.0))]
    stalled = list(calm)
    stalled[20] = ("q01", 4018.0)  # the machine froze for four seconds
    g0, r0, late0 = numbers(window(calm))
    g1, r1, late1 = numbers(window(stalled))
    assert math.isclose(g0, math.sqrt(18.0 * 965.0))
    assert math.isclose(g1, g0), "one execution in thirty is not the typical one"
    assert math.isclose(r0, 60 / (30 * 0.983))
    assert math.isclose(r1, 60 / (30 * 0.983 + 4.0)) and r1 < 0.9 * r0
    assert late0 == 0.0
    assert math.isclose(late1, 100 / 60), "and it is counted"


def test_late_stmt_share_on_a_hand_made_window():
    read = harness.load_reader("client_clock")
    # medians: a 100, b 10. Late (over 1.5 times): a's 151 and 400, b's 16;
    # a's 149 is not over
    sts = window([("a", 100.0), ("a", 149.0), ("a", 151.0), ("a", 90.0),
                  ("a", 400.0), ("a", 95.0), ("a", 100.0),
                  ("b", 10.0), ("b", 16.0), ("b", 9.0)])
    ctx = harness.Context()
    ctx.statements, ctx.t0 = sts, 100.0
    assert math.isclose(read(ctx, "late_stmt_share"), 30.0)
    # a failed statement is beyond any limit, so it is late
    ctx.statements = window([("a", 100.0)] * 5)
    assert read(ctx, "late_stmt_share") == 0.0
    ctx.statements[2].error = "boom"
    assert math.isclose(read(ctx, "late_stmt_share"), 20.0)
    ctx.statements = []
    assert read(ctx, "late_stmt_share") is None


def test_queries_per_s_counts_correct_statements_over_the_whole_window():
    assert e2e.queries_per_s(28, 10.0, 66.0) == 0.5
    # a stall stretches the window and lowers the rate
    assert e2e.queries_per_s(28, 10.0, 80.0) < 0.5
    # a wrong answer does not count
    assert e2e.queries_per_s(27, 10.0, 66.0) < 0.5
    assert e2e.queries_per_s(1, 5.0, 5.0) is None


def test_latency_from_send_or_from_due_and_failures_are_beyond_any_limit():
    st = SimpleNamespace(sent_s=103.0, done_s=104.5, due_s=2.0, error=None)
    assert math.isclose(e2e.latency_ms(st, False, 100.0), 1500.0)
    # open loop: from the instant it was due (t0 + 2.0), so the second
    # it waited for a sender or a queue counts
    assert math.isclose(e2e.latency_ms(st, True, 100.0), 2500.0)
    bad = SimpleNamespace(sent_s=103.0, done_s=104.5, due_s=2.0, error="boom")
    assert e2e.latency_ms(bad, True, 100.0) == e2e.BEYOND_ANY_LIMIT_MS


def test_a_failed_statement_still_makes_the_run_not_correct():
    # the median sets one failure in thirty aside; the comparison does not
    sts = window([("q01", 18.0)] * 30)
    sts[3].error, sts[3].rows = "boom", None
    for st in sts:
        st.key = "k"
    nums = harness.compare({}, None, sts[3:4])["numbers"]
    assert nums["statements_failed"] == 1 > harness.LIMITS["statements_failed"]
    assert not all(nums[k] <= harness.LIMITS[k] for k in nums)
    # and it does not count as answered
    sts[3].correct = False
    assert numbers(sts)[1] < 30 / (30 * 0.018)
