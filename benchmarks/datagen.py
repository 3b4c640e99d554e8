"""The reference's child: generated tables -> sqlite -> expected rows.

Runs pinned to the CPU (``JAX_PLATFORMS=cpu`` for this child alone). It
is the one place where the program's table generator meets the
reference: the columns come from the ``tpch`` connector (so that the
servers, which read the same column cache, and the reference hold the
same tables), are checked against the row counts and checksums that the
configuration's file states, and are loaded into sqlite by
``reference.py``. Everything it makes is kept under ``.tpch_cache/`` of
the checkout and reused by later runs.

Lines on stdout, one JSON object each: ``{"ref": "data"}`` once every
column is in the column cache, ``{"ref": "ready"}`` once every
statement asked for has its expected rows on disk.
"""

from __future__ import annotations

import json
import os
import sys
import time
import zlib

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reference  # noqa: E402


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def checksum(arr) -> int:
    import numpy as np

    if arr.dtype == object or arr.dtype.kind == "U":
        return zlib.crc32("\x00".join(arr.tolist()).encode())
    return zlib.crc32(np.ascontiguousarray(arr.astype("<i8")).tobytes())


def build_db(schema: str, tables: dict, db_path: str, stated: dict) -> dict:
    """Loads the columns ``tables`` lists ({table: [column, ...]}) and
    returns what was found: rows and a checksum per table."""
    import sqlite3

    from trino_tpu import types as T
    from trino_tpu.connectors.tpch.connector import TpchConnector
    from trino_tpu.connectors.tpch.generator import SCHEMAS

    data = TpchConnector().data(schema)
    found = {}
    cols = {}
    for table, names in tables.items():
        types = dict(SCHEMAS[table].columns)
        crc = 0
        for name in names:
            arr = data.column(table, name)
            cols[table, name] = (arr, types[name])
            crc = zlib.crc32(str(checksum(arr)).encode(), crc)
        found[table] = {"rows": int(data.row_count(table)), "checksum": crc}
    say({"ref": "data"})
    if stated:
        for table, want in stated.items():
            if found.get(table) != want:
                raise SystemExit(
                    f"generated table {table} is {found.get(table)}, the "
                    f"configuration states {want}: the generator changed"
                )
    if os.path.exists(db_path):
        return found
    tmp = f"{db_path}.tmp"
    if os.path.exists(tmp):
        os.remove(tmp)
    conn = sqlite3.connect(tmp)
    conn.execute("PRAGMA journal_mode=OFF")
    conn.execute("PRAGMA synchronous=OFF")
    for table, names in tables.items():
        decl = []
        for name in names:
            typ = cols[table, name][1]
            decl.append((name, "TEXT" if isinstance(typ, T.VarcharType)
                         else "INTEGER"))
        reference.create_table(conn, table, decl)
        n = found[table]["rows"]
        chunk = 1_000_000
        for lo in range(0, n, chunk):
            lists = [cols[table, name][0][lo:lo + chunk].tolist()
                     for name in names]
            reference.insert_rows(conn, table, len(names), zip(*lists))
    conn.commit()
    conn.close()
    os.replace(tmp, db_path)
    return found


def main() -> int:
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        say({"ref": "refused: not pinned to the CPU"})
        return 2
    req = json.load(sys.stdin)
    t0 = time.monotonic()
    os.makedirs(req["dir"], exist_ok=True)
    db_path = os.path.join(req["dir"], "ref.db")
    fresh = not os.path.exists(db_path)
    found = build_db(req["schema"], req["tables"], db_path,
                     req.get("stated") or {})
    conn = reference.connect(db_path, control=bool(req.get("control")))
    if fresh:
        reference.create_indexes(conn, req.get("indexes") or {})
    built_s = time.monotonic() - t0
    for st in req["statements"]:
        path = os.path.join(req["dir"], st["key"] + ".json")
        if os.path.exists(path):
            continue
        rows = reference.expected_rows(conn, st["ref_sql"])
        with open(path + ".tmp", "w") as fh:
            json.dump(rows, fh)
        os.replace(path + ".tmp", path)
    conn.close()
    say({"ref": "ready", "tables": found, "built_s": round(built_s, 1),
         "total_s": round(time.monotonic() - t0, 1)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
