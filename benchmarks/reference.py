"""The plain reference: sqlite3 over the same generated tables, in
exact integer arithmetic.

Imports nothing of the program. Decimals are loaded as integers at
their own scale (cents), dates as days since 1970-01-01, so every sum
the templates ask for is an exact integer and the comparison with the
served rows has the limit 0. Each template has a ``<name>.ref.sql.txt``
beside it: the same statement in sqlite's dialect over those integer
tables. (The translation idea and the row comparison are copied from
trino_tpu/testing/golden.py, which loads decimals as REAL and compares
within 1e-6 relative — too loose to see a float32 sum.)
"""

from __future__ import annotations

import datetime
import hashlib
import json
import os
import re
import sqlite3
from decimal import ROUND_HALF_UP, Decimal
from fractions import Fraction

HERE = os.path.dirname(os.path.abspath(__file__))
EPOCH = datetime.date(1970, 1, 1)


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - EPOCH).days


def iso(day: int) -> str:
    return (EPOCH + datetime.timedelta(days=int(day))).isoformat()


def _plus_year(iso_date: str) -> int:
    d = datetime.date.fromisoformat(iso_date)
    return (d.replace(year=d.year + 1) - EPOCH).days


#: filters a placeholder of a reference statement may name:
#: ``{DATE|days}``. A literal may stand in place of a parameter name.
FILTERS = {
    "days": lambda v: str(days(v)),
    "days_plus_1y": lambda v: str(_plus_year(v)),
    "x100": lambda v: str(int(Decimal(v) * 100)),
}


def render(text: str, params: dict) -> str:
    """Fills ``{NAME}`` and ``{NAME|filter}`` from ``params``. A name
    that is no parameter is taken as a literal (``{1998-12-01|days}``)."""

    def sub(m):
        name, _, filt = m.group(1).partition("|")
        value = params.get(name, name if filt else None)
        if value is None:
            raise KeyError(f"template parameter {name!r} has no value")
        return FILTERS[filt](value) if filt else str(value)

    return re.sub(r"\{([^{}]+)\}", sub, text)


def statement_key(schema: str, template: str, params: dict,
                  ref_sql: str, data_id: str) -> str:
    h = hashlib.sha256(json.dumps(
        [schema, template, params, ref_sql, data_id], sort_keys=True
    ).encode()).hexdigest()[:24]
    return f"{template}-{h}"


# ---------------------------------------------------------------------------
# building the database (called from datagen.py, which hands in columns)
# ---------------------------------------------------------------------------


def create_table(conn: sqlite3.Connection, table: str,
                 columns: list[tuple[str, str]]) -> None:
    cols = ", ".join(f"{n} {t}" for n, t in columns)
    conn.execute(f"CREATE TABLE {table} ({cols})")


def insert_rows(conn: sqlite3.Connection, table: str, n_columns: int,
                rows) -> None:
    marks = ",".join("?" * n_columns)
    conn.executemany(f"INSERT INTO {table} VALUES ({marks})", rows)


def create_indexes(conn: sqlite3.Connection, indexes: dict) -> None:
    for table, cols in indexes.items():
        for col in cols:
            conn.execute(f"CREATE INDEX idx_{table}_{col} ON {table} ({col})")
    conn.execute("ANALYZE")
    conn.commit()


# ---------------------------------------------------------------------------
# lower-precision control: the same reference with every sum in float32
# ---------------------------------------------------------------------------


class Float32Sum:
    """``sum`` accumulated left to right in float32, as a program with
    narrowed lanes would. Replaces sqlite's exact integer ``sum`` in the
    control only."""

    def __init__(self):
        self.vals: list = []

    def step(self, v):
        if v is not None:
            self.vals.append(v)

    def finalize(self):
        if not self.vals:
            return None
        import numpy as np

        acc = np.cumsum(np.asarray(self.vals, dtype=np.float32),
                        dtype=np.float32)[-1]
        return int(round(float(acc)))


def connect(path: str, control: bool = False) -> sqlite3.Connection:
    conn = sqlite3.connect(path)
    if control:
        conn.create_aggregate("sum", 1, Float32Sum)
    return conn


def expected_rows(conn: sqlite3.Connection, ref_sql: str) -> list[list]:
    return [list(r) for r in conn.execute(ref_sql).fetchall()]


def served_form(columns_spec: list[dict], ref_row: list) -> list:
    """A row of the reference in the form the served path gives it:
    decimals as strings at their scale, averages rounded half up, dates
    as ISO text. The control's rows take the program's place in it."""
    out, i = [], 0
    for spec in columns_spec:
        kind, v = spec["kind"], ref_row[i]
        if kind == "avg":
            s, n = ref_row[i], ref_row[i + 1]
            i += 2
            if not n or s is None:
                out.append(None)
                continue
            q = (Decimal(int(s)) / Decimal(int(n))).quantize(
                Decimal(1), rounding=ROUND_HALF_UP)
            out.append(str(q.scaleb(-spec["scale"])))
            continue
        i += 1
        if v is None:
            out.append(None)
        elif kind == "decimal":
            out.append(str(Decimal(int(v)).scaleb(-spec["scale"])))
        elif kind == "date":
            out.append(iso(v))
        else:
            out.append(v)
    return out


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def compare_statement(columns_spec: list[dict], ordered: bool,
                      got: list[list], ref: list[list]) -> dict:
    """Compares the rows a client received with the reference's.

    Returns ``{"exact_mismatches", "decimal_gap_ulp", "avg_gap_ulp",
    "detail"}``: the number of cells (or rows) that differ where the
    comparison is exact — keys, strings, dates, counts, row count and
    order; the widest gap of a summed decimal in units of its last
    place; and the widest gap of an average from the exact quotient,
    in units of the served last place.
    """
    out = {"exact_mismatches": 0, "decimal_gap_ulp": 0.0,
           "avg_gap_ulp": 0.0, "detail": ""}

    def note(msg):
        if not out["detail"]:
            out["detail"] = msg[:300]

    # unfold the reference's columns: an avg takes two (sum, count)
    ref_rows = []
    for r in ref:
        row, i = [], 0
        for spec in columns_spec:
            if spec["kind"] == "avg":
                row.append((r[i], r[i + 1]))
                i += 2
            else:
                row.append(r[i])
                i += 1
        ref_rows.append(row)
    if len(got) != len(ref_rows):
        out["exact_mismatches"] += abs(len(got) - len(ref_rows)) or 1
        note(f"row count {len(got)} != {len(ref_rows)}")
        return out

    def canon(row, is_ref):
        """A row as comparable cells: exact cells, then numeric cells."""
        exact, sums, avgs = [], [], []
        for spec, v in zip(columns_spec, row):
            kind = spec["kind"]
            if kind == "exact":
                exact.append(v)
            elif kind == "date":
                exact.append(iso(v) if is_ref and v is not None else v)
            elif kind == "decimal":
                if v is None:
                    sums.append(None)
                elif is_ref:
                    sums.append(int(v))
                else:  # '123.4500' in units of its last place
                    sums.append(Decimal(str(v)).scaleb(spec["scale"]))
            elif kind == "avg":
                if is_ref:
                    s, n = v
                    avgs.append(None if not n else Fraction(int(s), int(n)))
                else:
                    avgs.append(None if v is None else
                                Fraction(Decimal(str(v))) * 10 ** spec["scale"])
            else:
                raise ValueError(f"unknown column kind {kind!r}")
        return exact, sums, avgs

    g = [canon(r, False) for r in got]
    e = [canon(r, True) for r in ref_rows]
    if not ordered:
        key = lambda c: json.dumps([c[0], [str(x) for x in c[1]]],
                                   sort_keys=True, default=str)
        g.sort(key=key)
        e.sort(key=key)
    for i, ((ge, gs, ga), (ee, es, ea)) in enumerate(zip(g, e)):
        if ge != ee:
            out["exact_mismatches"] += sum(
                1 for a, b in zip(ge, ee) if a != b
            ) or 1
            note(f"row {i}: {ge} != {ee}")
        for a, b in zip(gs, es):
            if a is None or b is None:
                if a is not b:
                    out["exact_mismatches"] += 1
                    note(f"row {i}: null mismatch {a} vs {b}")
                continue
            gap = abs(float(Fraction(a) - Fraction(b)))
            if gap > out["decimal_gap_ulp"]:
                out["decimal_gap_ulp"] = gap
                note(f"row {i}: decimal {a} vs {b}")
        for a, b in zip(ga, ea):
            if a is None or b is None:
                if a is not b:
                    out["exact_mismatches"] += 1
                continue
            gap = abs(float(a - b))
            if gap > out["avg_gap_ulp"]:
                out["avg_gap_ulp"] = gap
    return out
