"""``kernels.gather_rows``: a page read at one index vector in stacked
gathers — every column as 32-bit words side by side, validity lanes as
bits of one more word (ISSUE 36) — is ``d[idx]``, ``v[idx]`` bit for
bit, for every dtype the engine puts in a ``Column``; and
``LocalExecutor._compact``, its first caller, makes the live mask from
the count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.connectors.tpch.queries import QUERIES
from trino_tpu.engine import QueryRunner
from trino_tpu.exec import kernels as K
from trino_tpu.page import Column, Page
from trino_tpu.testing.golden import (
    assert_rows_match,
    load_tpch_sqlite,
    to_sqlite,
)

ROWS = 4096
RNG = np.random.default_rng(36)


def _ints(dtype, lanes=()):
    info = np.iinfo(dtype)
    return RNG.integers(
        info.min, info.max, (ROWS, *lanes), dtype=dtype, endpoint=True)


def _floats(dtype):
    """Finite values among NaNs of several payloads, both zeros and
    both infinities."""
    bits = np.dtype(dtype).itemsize * 8
    u = np.dtype(f"uint{bits}")
    x = RNG.standard_normal(ROWS).astype(dtype)
    quiet = (0x7FF8 << 48) if bits == 64 else (0x7FC0 << 16)
    special = np.array(
        [quiet, quiet | 1, quiet | 0xBEEF, (1 << (bits - 1)) | quiet | 7,
         1 << (bits - 1), 0], dtype=u,
    ).view(dtype)
    x[RNG.integers(0, ROWS, 600)] = np.tile(special, 100)
    x[RNG.integers(0, ROWS, 20)] = np.inf
    x[RNG.integers(0, ROWS, 20)] = -np.inf
    return x


def _valid():
    return RNG.random(ROWS) < 0.7


def _nullable(k):
    return {
        f"n{i}": (_ints(np.int32) if i % 3 else _ints(np.int64), _valid())
        for i in range(k)
    }


#: name -> page as {column: (data, valid)}, host arrays
PAGES = {
    "int64": {"a": (_ints(np.int64), None)},
    "int32": {"a": (_ints(np.int32), None)},
    "int16_int8": {"a": (_ints(np.int16), None), "b": (_ints(np.int8), None)},
    "bool": {"a": (RNG.random(ROWS) < 0.5, None)},
    "float64_nan_payloads": {"a": (_floats(np.float64), None)},
    "float32_nan_payloads": {"a": (_floats(np.float32), None)},
    "two_limb_decimal": {"a": (_ints(np.int64, (2,)), _valid())},
    "hash_coded_varchar": {"a": (
        np.stack([_ints(np.int64), np.arange(ROWS, dtype=np.int64)], axis=1),
        _valid(),
    )},
    "dictionary_codes": {"a": (
        RNG.integers(0, 25, ROWS, dtype=np.int32), _valid())},
    "q3_lineitem": {
        "k": (_ints(np.int64), None), "p": (_ints(np.int64), None),
        "d": (_ints(np.int64), None), "s": (_ints(np.int32), None),
    },
    "nullable_1": _nullable(1),
    "nullable_32": _nullable(32),
    "nullable_33": _nullable(33),
    "wide_sketch_lane": {
        "k": (_ints(np.int64), _valid()),
        "hll": (_ints(np.int8, (512,)), None),
        "pool": (_ints(np.int32, (K.GATHER_WIDE_WORDS + 1,)), _valid()),
    },
    "at_the_wide_bound": {
        "a": (_ints(np.int32, (K.GATHER_WIDE_WORDS,)), None),
        "b": (_ints(np.int64, (K.GATHER_WIDE_WORDS // 2,)), _valid()),
    },
    "mixed": {
        "a": (_ints(np.int64), _valid()), "b": (RNG.random(ROWS) < 0.5, _valid()),
        "c": (_floats(np.float64), None), "d": (_ints(np.int64, (2,)), None),
        "e": (_ints(np.int8), _valid()), "f": (_floats(np.float32), _valid()),
        "g": (_ints(np.uint64), None), "h": (_ints(np.uint32), None),
    },
    "zero_columns": {},
}

INDICES = {
    "row_order": np.sort(RNG.integers(0, ROWS, 1500)).astype(np.int32),
    "out_of_order": RNG.integers(0, ROWS, 5000).astype(np.int32),
    "no_positions": np.zeros(0, np.int32),
}


def _device(page):
    return {
        name: (jnp.asarray(d), None if v is None else jnp.asarray(v))
        for name, (d, v) in page.items()
    }


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def _check(page, idx):
    got = jax.jit(K.gather_rows)(_device(page), jnp.asarray(idx))
    assert sorted(got) == sorted(page)
    for name, (d, v) in page.items():
        _same_bits(got[name][0], d[idx])
        if v is None:
            assert got[name][1] is None
        else:
            _same_bits(got[name][1], v[idx])


@pytest.mark.parametrize("idx", list(INDICES))
@pytest.mark.parametrize("page", list(PAGES))
def test_gather_rows_is_the_per_column_gather_bit_for_bit(page, idx):
    _check(PAGES[page], INDICES[idx])


def test_gather_rows_of_an_empty_page():
    page = {
        name: (d[:0], None if v is None else v[:0])
        for name, (d, v) in PAGES["mixed"].items()
    }
    _check(page, INDICES["no_positions"])


@pytest.mark.parametrize("width", [1, 2, 4, 8])
def test_gather_rows_at_any_stack_width(monkeypatch, width):
    """The stack width is a constant set from a table of the chip
    (PERF.md, PR 36): whatever it is, the answer is the same."""
    monkeypatch.setattr(K, "GATHER_STACK_WORDS", width)
    _check(PAGES["mixed"], INDICES["out_of_order"])
    _check(PAGES["nullable_33"], INDICES["row_order"])


def _sig(page):
    return [(d.dtype, d.shape[1:], v is not None) for d, v in page.values()]


@pytest.mark.parametrize("page,words,alone", [
    ("int64", 2, 0), ("bool", 1, 0), ("q3_lineitem", 7, 0),
    ("two_limb_decimal", 5, 0), ("nullable_32", 11 * 2 + 21 + 1, 0),
    ("nullable_33", 11 * 2 + 22 + 2, 0), ("wide_sketch_lane", 3, 2),
    ("at_the_wide_bound", 17, 0), ("zero_columns", 0, 0),
])
def test_gather_plan_counts_what_gather_rows_builds(page, words, alone):
    """Words stacked and gathers, from the layout alone — and the same
    number of gathers in the program ``gather_rows`` traces."""
    stacked, gathers = K.gather_plan(_sig(PAGES[page]))
    assert stacked == words
    assert gathers == alone + -(-words // K.GATHER_STACK_WORDS)
    txt = jax.jit(K.gather_rows).lower(
        _device(PAGES[page]), jnp.asarray(INDICES["row_order"])
    ).as_text()
    ops = [ln for ln in txt.splitlines() if '"stablehlo.gather"(' in ln]
    assert len(ops) == gathers
    assert not any("xi1>" in ln for ln in ops)  # no validity lane as pred


# ---- the executor's compaction ---------------------------------------------


@pytest.fixture(scope="module")
def runner():
    return QueryRunner.tpch("tiny")


def _page(mask):
    n = len(mask)
    rows = np.arange(n, dtype=np.int64)
    cols = [
        Column(T.BIGINT, jnp.asarray(rows * 7 - 3), jnp.asarray(rows % 5 != 0)),
        Column(T.INTEGER, jnp.asarray(rows.astype(np.int32)), None),
        Column(T.DOUBLE, jnp.asarray(rows / 3.0), None),
        Column(
            T.DecimalType(38, 2),
            jnp.asarray(np.stack([rows, -rows], axis=1)), None,
        ),
    ]
    return Page(["a", "b", "c", "d"], cols, jnp.asarray(mask))


@pytest.mark.parametrize("extra", [0, 3000])
@pytest.mark.parametrize("live", ["none", "all", "some", "one"])
def test_compact_puts_live_rows_first_and_masks_by_their_count(
        runner, live, extra):
    n = 8192
    mask = {
        "none": np.zeros(n, bool), "all": np.ones(n, bool),
        "some": RNG.random(n) < 0.3, "one": np.arange(n) == 4097,
    }[live]
    page = _page(mask)
    out = runner.executor._compact(page, extra_capacity=extra)
    n_live = int(mask.sum())
    assert out.packed and out.known_rows == n_live
    assert out.capacity <= page.capacity
    assert out.capacity >= min(n_live + extra, page.capacity)
    np.testing.assert_array_equal(
        np.asarray(out.mask), np.arange(out.capacity) < n_live)
    at = np.flatnonzero(mask)
    for before, after in zip(page.columns, out.columns):
        _same_bits(np.asarray(after.data)[:n_live], np.asarray(before.data)[at])
        assert (before.valid is None) == (after.valid is None)
        if before.valid is not None:
            _same_bits(
                np.asarray(after.valid)[:n_live], np.asarray(before.valid)[at])
    # an already packed page that cannot shrink is handed back as it is
    assert runner.executor._compact(out, extra_capacity=extra) is out


def test_compact_notes_its_shape_and_its_gathers_on_the_dispatch_span(runner):
    from trino_tpu import telemetry

    root = telemetry.Span(name="statement", kind="query")
    telemetry.set_active_span(root)
    try:
        mask = RNG.random(8192) < 0.1
        out = runner.executor._compact(_page(mask))
    finally:
        telemetry.set_active_span(None)
    (span,) = [sp for sp in root.walk() if sp.name == "dispatch"]
    assert span.attrs["program"] == "compact"
    assert span.attrs["rows_in"] == 8192
    assert span.attrs["rows_out"] == out.capacity
    assert span.attrs["columns"] == 4
    # 2 + 1 + 2 + 4 words and one of validity bits, in stacks
    assert span.attrs["gather_ops"] == -(-10 // K.GATHER_STACK_WORDS)
    totals = telemetry.span_totals(root)
    assert totals["compactions"] == 1
    assert totals["compact_gather_ops"] == span.attrs["gather_ops"]


@pytest.fixture(scope="module")
def oracle():
    data = QueryRunner.tpch("tiny").metadata.connector("tpch").data("tiny")
    return load_tpch_sqlite(data)


@pytest.mark.parametrize("q", ["q03", "q18"])
def test_the_compacting_queries_equal_the_oracle(runner, oracle, q):
    sql = QUERIES[q]
    result = runner.execute(sql)
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(result.rows, expected, ordered=result.ordered)
    compactions = [
        sp for sp in result.trace.find(name="dispatch")
        if sp.attrs["program"] == "compact"
    ]
    assert compactions
    assert all(
        sp.attrs["gather_ops"] <= max(1, sp.attrs["columns"])
        for sp in compactions
    )
