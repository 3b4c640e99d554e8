"""TPC-H connector: schemas tiny/sf1/sf5/sf10/sf100 of generated tables."""

from __future__ import annotations

import json
import os

import numpy as np

from trino_tpu.connectors.base import (
    ColumnStats,
    Connector,
    Split,
    TableSchema,
    TableStats,
    compute_column_stats,
)
from trino_tpu.connectors.tpch.generator import SCHEMAS, SCHEMA_SF, TpchData

__all__ = ["TpchConnector"]


class TpchConnector(Connector):
    def __init__(self):
        self._data: dict[float, TpchData] = {}
        self._stats: dict[tuple[float, str], dict[str, ColumnStats]] = {}

    def data(self, schema: str) -> TpchData:
        sf = self._sf(schema)
        if sf not in self._data:
            self._data[sf] = TpchData(sf)
        return self._data[sf]

    @staticmethod
    def _sf(schema: str) -> float:
        if schema in SCHEMA_SF:
            return SCHEMA_SF[schema]
        if schema.startswith("sf"):
            try:
                return float(schema[2:])
            except ValueError:
                pass
        raise KeyError(f"unknown tpch schema: {schema}")

    def list_schemas(self) -> list[str]:
        return list(SCHEMA_SF)

    def list_tables(self, schema: str) -> list[str]:
        return list(SCHEMAS)

    def table_schema(self, schema: str, table: str) -> TableSchema:
        return SCHEMAS[table]

    #: dbgen writes ``orders`` by ascending key and ``lineitem`` order by
    #: order (``generator.py``: ``np.repeat(orders.orderkey, ...)``) —
    #: what the reference's TpchMetadata.getTableProperties declares
    _SORTED_BY = {"orders": "o_orderkey", "lineitem": "l_orderkey"}

    def sorted_by(self, schema: str, table: str) -> str | None:
        return self._SORTED_BY.get(table)

    def row_count(self, schema: str, table: str) -> int:
        return self.data(schema).row_count(table)

    def column_stats(self, schema: str, table: str, column: str) -> ColumnStats:
        """Exact per-column stats (the reference tpch connector ships
        column statistics the same way, plugin/trino-tpch
        TpchMetadata.getTableStatistics), computed LAZILY per column so
        planning a query never materializes columns it doesn't touch
        (generating SF100 comment text just for stats would take
        minutes). Disk-cached incrementally beside the data cache; the
        generated data is deterministic per (sf, table), so the cache
        never goes stale."""
        sf = self._sf(schema)
        key = (sf, table)
        cols = self._stats.setdefault(key, {})
        if column in cols:
            return cols[column]
        data = self.data(schema)
        path = data.stats_path(table)
        if not cols and path is not None and os.path.exists(path):
            with open(path) as f:
                raw = json.load(f)
            # older cache files stored integer bounds as floats; value-
            # range packing requires exact ints (2^53 rounding), so
            # coerce integral floats back (exact below 2^53)
            for v in raw.values():
                for b in ("lo", "hi"):
                    x = v.get(b)
                    if (
                        isinstance(x, float) and x.is_integer()
                        and abs(x) < 2**53
                    ):
                        v[b] = int(x)
            cols.update({c: ColumnStats(**v) for c, v in raw.items()})
            if column in cols:
                return cols[column]
        cols[column] = compute_column_stats(data.column(table, column))
        if path is not None:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({c: vars(s) for c, s in cols.items()}, f)
            os.replace(tmp, path)
        return cols[column]

    def table_stats(self, schema: str, table: str) -> TableStats:
        """Full-table stats: forces every column (tests use this; the
        planner prefers column_stats)."""
        n = self.data(schema).row_count(table)
        cols = {
            c: self.column_stats(schema, table, c)
            for c in SCHEMAS[table].column_names
        }
        return TableStats(float(n), cols)

    def scan(
        self, schema: str, table: str, columns: list[str], split: Split | None = None
    ) -> dict[str, np.ndarray]:
        data = self.data(schema)
        out = {}
        for c in columns:
            arr = data.column(table, c)
            if split is not None:
                arr = arr[split.start : split.start + split.count]
            out[c] = arr
        return out
