"""A whole run of the harness at the rehearsal schema on the CPU, with
the look for a chip skipped (through ``run.run``'s test hook, which the
command line cannot reach): sound, it comes out correct; with the timed
path broken underneath — an answer altered where the client receives
it — it comes out NOT correct. And the lower-precision control comes
out not correct."""

import json
import os
import types
from decimal import Decimal

import control
import run as harness
import supervisor


def drive(capsys, workload, hooks, seconds="3"):
    args = harness.parse(["--workload", workload, "--seed", "2147483659",
                          "--seconds", seconds, "--trace", "0", "--rehearse"])
    os.environ["JAX_PLATFORMS"] = "cpu"
    rc = harness.run(args, dict(hooks, skip_device_check=True))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and out, "the hook lets the line through"
    return json.loads(out[-1])


def test_sound_run_is_correct(capsys):
    res = drive(capsys, "sf1_power", {})
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % 4 == 0 and res["attempted"] >= 4, "whole passes"
    assert list(res)[-1] == "compared", "the numbers compared come last"
    assert set(res["metrics"]) == {"query_geomean_ms", "queries_per_s", "setup_s"}
    assert all(v["value"] <= v["limit"] for v in res["compared"].values())
    assert res["device"]["platform"] == "cpu"  # named from the server, as found


def broken_client():
    """The real client, with the last cent of the first decimal of every
    answer that has one moved by one: one unit in the last place."""
    real = supervisor.load_client()

    class Altered(real.StatementClient):
        def execute(self, sql):
            columns, rows = super().execute(sql)
            for j, col in enumerate(columns):
                if (col.get("type", "").startswith("decimal") and rows
                        and rows[0][j] is not None):
                    v = Decimal(rows[0][j])
                    rows[0][j] = str(v + Decimal(1).scaleb(v.as_tuple().exponent))
                    break
            return columns, rows

    return types.SimpleNamespace(StatementClient=Altered,
                                 QueryError=real.QueryError)


def test_broken_timed_path_is_not_correct(capsys):
    res = drive(capsys, "sf1_throughput", {"client_mod": broken_client()})
    assert res["attempted"] % 4 == 0, "whole passes of each stream"
    assert res["correct"] is False
    cmp_ = res["compared"]
    assert cmp_["statements_wrong"]["value"] > 0
    assert cmp_["decimal_gap_ulp"]["value"] >= 1.0
    assert cmp_["statements_failed"]["value"] == 0
    # a wrong answer is not a completed statement
    sent = res["attempted"] / res["window_s"]
    assert res["metrics"]["queries_per_s"]["value"] < sent


def test_lower_precision_control_is_not_correct(capsys):
    rc = control.main(["--workload", "sf1_power", "--seeds", "3",
                       "--schema", "tiny"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert rc == 0 and len(lines) == 3
    for line in lines:
        assert line["correct"] is False
        gap = line["compared"]["decimal_gap_ulp"]
        assert gap["value"] > 1000 * max(gap["limit"], 1.0)


def test_query_list_reader_fails_on_a_late_subset():
    import pytest

    read = harness.load_reader("query_list")
    sts = [types.SimpleNamespace(query_id=f"q{i}", cls="long") for i in range(10)]
    ctx = types.SimpleNamespace(
        statements=sts,
        query_list=[{"query_id": f"q{i}", "queued_time_ms": 2.0 * i}
                    for i in range(1, 10)])
    assert read(ctx, "queued_time_ms") == 10.0  # nine of ten found
    ctx.query_list = ctx.query_list[4:]
    with pytest.raises(RuntimeError, match="5 of the window's 10"):
        read(ctx, "queued_time_ms")
