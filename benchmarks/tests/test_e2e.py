"""The arithmetic of the end-to-end metrics on hand-made latency lists,
and that a stall moves each of them."""

import math
from types import SimpleNamespace

import e2e


def test_query_geomean_is_geomean_of_template_means():
    lat = {"a": [100.0, 300.0], "b": [50.0], "c": [400.0, 400.0, 400.0]}
    # means 200, 50, 400 -> (200*50*400) ** (1/3)
    assert math.isclose(e2e.query_geomean_ms(lat), (200 * 50 * 400) ** (1 / 3))
    stalled = {"a": [100.0, 300.0, 2000.0], "b": [50.0], "c": [400.0] * 3}
    assert e2e.query_geomean_ms(stalled) > 1.5 * e2e.query_geomean_ms(lat)
    assert e2e.query_geomean_ms({}) is None


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
    # one failure in thirty moves a template's mean, and so the geomean
    lat = {"a": [1500.0] * 29 + [e2e.latency_ms(bad, False, 100.0)], "b": [100.0]}
    assert e2e.query_geomean_ms(lat) > 5 * e2e.query_geomean_ms(
        {"a": [1500.0] * 30, "b": [100.0]})
