"""The comparison that decides ``correct``, on hand-made rows."""

import reference

SPEC = [
    {"name": "k", "kind": "exact"},
    {"name": "s", "kind": "decimal", "scale": 2},
    {"name": "a", "kind": "avg", "scale": 2},
    {"name": "d", "kind": "date"},
]
#      key  sum(cents)  avg: sum, count     days since 1970-01-01
REF = [["x", 123456, 37900600, 14838, 8766], ["y", 5, 10, 3, 9131]]


def test_served_form_round_trips_to_no_gap():
    got = [reference.served_form(SPEC, r) for r in REF]
    assert got[0] == ["x", "1234.56", "25.54", "1994-01-01"]
    assert got[1] == ["y", "0.05", "0.03", "1995-01-01"]
    r = reference.compare_statement(SPEC, True, got, REF)
    assert r["exact_mismatches"] == 0 and r["decimal_gap_ulp"] == 0.0
    assert 0 < r["avg_gap_ulp"] <= 0.5


def test_one_cent_off_is_seen():
    got = [reference.served_form(SPEC, r) for r in REF]
    got[0][1] = "1234.57"
    r = reference.compare_statement(SPEC, True, got, REF)
    assert r["decimal_gap_ulp"] == 1.0


def test_wrong_key_date_order_and_row_count_are_exact_mismatches():
    good = [reference.served_form(SPEC, r) for r in REF]
    for alter in (
        lambda g: g[0].__setitem__(0, "z"),
        lambda g: g[1].__setitem__(3, "1995-01-02"),
        lambda g: g.reverse(),
        lambda g: g.pop(),
    ):
        got = [list(r) for r in good]
        alter(got)
        assert reference.compare_statement(SPEC, True, got, REF)["exact_mismatches"] > 0
    # an unordered statement may come back in any order
    got = [list(r) for r in reversed(good)]
    assert reference.compare_statement(SPEC, False, got, REF)["exact_mismatches"] == 0


def test_truncated_average_is_beyond_the_limit():
    got = [reference.served_form(SPEC, r) for r in REF]
    got[1][2] = "0.02"   # 10/3 cents = 3.33 -> 0.03; 0.02 is 1.33 away
    assert reference.compare_statement(SPEC, True, got, REF)["avg_gap_ulp"] > 1.0


def test_render_filters():
    out = reference.render(
        "{A|days} {1998-12-01|days} {N} {Q|x100} {A|days_plus_1y}",
        {"A": "1994-01-01", "N": "90", "Q": "0.05"})
    assert out == "8766 10561 90 5 9131"


def test_float32_sum_loses_cents():
    s = reference.Float32Sum()
    for _ in range(1000):
        s.step(12345678)
    assert s.finalize() != 1000 * 12345678
