"""Connector SPI.

The analog of the reference's connector SPI (SPI/connector/, 110
files): a ``Connector`` exposes metadata (tables, schemas) and a scan
path. The TPU twist: a scan yields *host numpy columns* (optionally a
row range of the table, the analog of a ConnectorSplit) which the
engine marshals to device pages; pruned columns are never produced
(projection pushdown, the analog of ConnectorMetadata.applyProjection).

A connector may also declare that a table's rows come back ascending
on one column (``Connector.sorted_by`` — the single-column case of the
reference's local properties, ConnectorTableProperties /
SortingProperty, which its ``tpch`` connector reports for ``orders``
and ``lineitem``). A GROUP BY over exactly that column of the whole
resident table then needs no sort: the runs are the groups
(``exec/kernels.py:run_group``, the StreamingAggregationOperator
analog), on one device and shard by shard on a mesh, where the hash
exchange between the partial and the final step is then satisfied in
place (``exec/mesh.py:exchange_in_place``). The declaration is checked
on the device, and a table that breaks it is grouped by sort — slower,
never wrong.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from trino_tpu import types as T

__all__ = [
    "TableSchema", "Connector", "Catalog", "Split", "ColumnDomain",
    "ColumnStats", "TableStats", "compute_column_stats",
    "WriteSink", "handle_table_schema", "rows_to_columns", "to_unscaled",
]


@dataclass(frozen=True)
class TableSchema:
    name: str
    columns: list[tuple[str, T.DataType]]

    @property
    def column_names(self) -> list[str]:
        return [c for c, _ in self.columns]

    def column_type(self, name: str) -> T.DataType:
        for c, t in self.columns:
            if c == name:
                return t
        raise KeyError(name)


@dataclass(frozen=True)
class Split:
    """A row range of a table — the unit of source parallelism
    (SPI/connector/ConnectorSplit.java analog).

    ``size_bytes`` is the estimated storage footprint of the range
    (0 = unknown) so schedulers can balance by bytes, not rows.
    ``stats`` carries per-column (name, lo, hi) storage-domain bounds
    from file footers — the scheduler-side pruning surface: a consumer
    holding a ColumnDomain may drop a split whose bounds are disjoint
    without ever opening the file."""

    table: str
    start: int
    count: int
    size_bytes: int = 0
    stats: tuple = ()

    def disjoint(self, domains: dict) -> bool:
        """True when any domain is provably disjoint with this split's
        column bounds (pruning-safe: unknown columns never prune)."""
        for col, lo, hi in self.stats:
            dom = domains.get(col)
            if dom is not None and dom.disjoint(lo, hi):
                return True
        return False


@dataclass(frozen=True)
class ColumnDomain:
    """Per-column value interval in STORAGE domain (ints for dates and
    short decimals, floats, python strings for varchar) — the
    TupleDomain-lite predicate model (SPI/predicate/TupleDomain.java,
    Domain.java collapsed to one range per column). ``None`` bounds are
    unbounded; strict flags mark open ends. Pruning-safe semantics
    only: a connector may skip storage units whose [min, max] cannot
    intersect the domain (NULLs never satisfy a comparison, so
    stats-disjoint units cannot contribute rows); the engine always
    re-applies the full filter."""

    lo: object = None
    hi: object = None
    lo_strict: bool = False
    hi_strict: bool = False

    def disjoint(self, stat_min, stat_max) -> bool:
        """True when no value in [stat_min, stat_max] can satisfy the
        domain (the rowgroup-skip test)."""
        try:
            if self.lo is not None and stat_max is not None:
                if stat_max < self.lo or (
                    self.lo_strict and stat_max == self.lo
                ):
                    return True
            if self.hi is not None and stat_min is not None:
                if stat_min > self.hi or (
                    self.hi_strict and stat_min == self.hi
                ):
                    return True
        except TypeError:
            return False  # incomparable stat types: never skip
        return False


@dataclass(frozen=True)
class ColumnStats:
    """Per-column statistics (SPI/statistics/ColumnStatistics.java
    analog). ``lo``/``hi`` are EXACT bounds in the column's storage
    order-domain (ints as-is, dates as day numbers, decimals as
    unscaled ints, doubles as floats; None for varchar) — the planner
    relies on exactness for value-range key packing, so connectors
    must only report bounds they can guarantee, and integer-domain
    bounds must be Python ints (float64 rounds beyond 2^53)."""

    ndv: float | None = None
    lo: float | int | None = None
    hi: float | int | None = None
    null_fraction: float = 0.0


@dataclass(frozen=True)
class TableStats:
    """Table statistics (SPI/statistics/TableStatistics.java analog)."""

    row_count: float
    columns: dict[str, ColumnStats] = field(default_factory=dict)


#: string columns beyond this row count estimate NDV from a sample
#: (exact np.unique over tens of millions of objects is a minutes-long
#: host sort; numeric columns stay exact — their unique is a fast
#: vectorized sort and their lo/hi must be exact anyway)
_NDV_SAMPLE_THRESHOLD = 2_000_000
_NDV_SAMPLE_SIZE = 500_000


def compute_column_stats(
    vals: np.ndarray, valid: np.ndarray | None = None
) -> ColumnStats:
    """Stats of a host column (the ANALYZE primitive). lo/hi are exact;
    NDV is exact except for very large string columns, where it uses
    the Duj1 estimator over a uniform sample (the reference's ANALYZE
    does the same kind of sampling via connector stats collection)."""
    n = len(vals)
    if valid is not None:
        vals = vals[valid]
    nulls = n - len(vals)
    if len(vals) == 0:
        return ColumnStats(ndv=0.0, null_fraction=1.0 if n else 0.0)
    is_str = vals.dtype == object or vals.dtype.kind in ("U", "S")
    if is_str and len(vals) > _NDV_SAMPLE_THRESHOLD:
        rng = np.random.default_rng(0)
        k = _NDV_SAMPLE_SIZE
        sample = vals[rng.choice(len(vals), size=k, replace=False)]
        uniq, counts = np.unique(sample, return_counts=True)
        d = len(uniq)
        f1 = int((counts == 1).sum())
        # Duj1: D = d / (1 - (N-k)/N * f1/k)
        denom = 1.0 - (len(vals) - k) / len(vals) * (f1 / k)
        ndv = min(float(d) / max(denom, 1e-9), float(len(vals)))
    else:
        ndv = float(len(np.unique(vals)))
    if is_str:
        lo = hi = None
    elif vals.dtype.kind in ("i", "u"):
        # keep integer bounds EXACT as Python ints: float64 rounds
        # beyond 2^53, and a lo rounded UP would corrupt value-range
        # key packing (distinct keys silently collapsing)
        lo, hi = int(vals.min()), int(vals.max())
    else:
        lo, hi = float(vals.min()), float(vals.max())
    return ColumnStats(
        ndv=ndv, lo=lo, hi=hi, null_fraction=nulls / n if n else 0.0
    )


class Connector:
    """Base connector: metadata + split enumeration + column scan."""

    #: False for live views (system tables): the executor must not
    #: device-cache their scans between queries
    cacheable = True

    #: True when scan() accepts ``domains`` and can prune storage units
    #: by footer statistics (the applyFilter capability flag,
    #: SPI/connector/ConnectorMetadata.java applyFilter)
    supports_domains = False

    def list_schemas(self) -> list[str]:
        return []

    def list_tables(self, schema: str) -> list[str]:
        raise NotImplementedError

    def table_schema(self, schema: str, table: str) -> TableSchema:
        raise NotImplementedError

    def row_count(self, schema: str, table: str) -> int:
        raise NotImplementedError

    def sorted_by(self, schema: str, table: str) -> str | None:
        """The column on which ``scan`` returns the table's rows in
        ascending order (of a whole-table scan; equal values adjacent),
        or None — the default — when no order is promised. A sort
        order, not just clustering: monotonicity is what the engine can
        check as it reads. A mesh scan relies on it across shards as
        well as inside one: it lays the rows over the devices as
        consecutive ranges of the scan's order, so shard i's keys
        precede shard i+1's and only a key whose run a boundary cuts is
        on two (``exec/mesh.py:ShardedPage.ordered_on``; checked on
        the devices there too, a broken promise costs one rerun)."""
        return None

    def table_stats(self, schema: str, table: str) -> TableStats:
        """Statistics for the planner (ConnectorMetadata.getTableStatistics
        analog, SPI/connector/ConnectorMetadata.java). The default
        reports the row count only; connectors override to add column
        stats."""
        return TableStats(float(self.row_count(schema, table)))

    def column_stats(self, schema: str, table: str, column: str):
        """Per-column stats — the planner asks column-by-column so a
        generator-backed connector never materializes columns the query
        doesn't touch (a full table_stats over SF100 lineitem would
        generate 60M comment strings just to throw them away).
        Default: delegate to table_stats."""
        return self.table_stats(schema, table).columns.get(column)

    def splits(
        self, schema: str, table: str, target_splits: int,
        domains: dict | None = None,
    ) -> list[Split]:
        """Enumerate splits. ``domains`` (column -> ColumnDomain) lets a
        supports_domains connector prune storage units at enumeration
        time; the default row-range division ignores it."""
        n = self.row_count(schema, table)
        target_splits = max(1, target_splits)
        per = -(-n // target_splits)
        out = []
        start = 0
        while start < n:
            c = min(per, n - start)
            out.append(Split(table, start, c))
            start += c
        return out or [Split(table, 0, 0)]

    def scan(
        self, schema: str, table: str, columns: list[str], split: Split | None = None
    ) -> dict[str, np.ndarray]:
        """Produce host arrays for the requested columns (row range).

        A value may also be a ``(values, valid)`` tuple for nullable
        columns (valid=None means all valid)."""
        raise NotImplementedError

    # ---- write path (ConnectorMetadata DDL + ConnectorPageSink analog,
    # SPI/connector/ConnectorMetadata.java, ConnectorPageSink.java) ----

    def create_table(self, schema: str, table: str, table_schema: TableSchema):
        raise NotImplementedError(f"{type(self).__name__} is read-only")

    def drop_table(self, schema: str, table: str):
        raise NotImplementedError(f"{type(self).__name__} is read-only")

    def insert(self, schema: str, table: str, columns: dict) -> int:
        """Append rows; ``columns`` maps column name ->
        (values, valid|None) host arrays. Returns the row count."""
        raise NotImplementedError(f"{type(self).__name__} is read-only")

    # ---- distributed write (TableWriter subsystem) -------------------

    def begin_insert(self, schema: str, table: str) -> dict:
        """Validate an INSERT target and return a JSON-safe write
        handle (ConnectorMetadata.beginInsert analog). MUST be free of
        side effects: the plan holding the handle may be replanned,
        speculated, or retried; all mutation happens in finish_write.

        Handle shape (shared across connectors; individual connectors
        may add keys): ``{"schema", "table", "mode": "insert",
        "columns": [[name, type_str], ...], "partition_by": [...]}``.
        The analyzer adds ``"catalog"`` after the call."""
        raise NotImplementedError(f"{type(self).__name__} is read-only")

    def begin_create(
        self, schema: str, table: str, table_schema: TableSchema,
        partition_by: list[str] | None = None,
        properties: dict | None = None,
    ) -> dict:
        """Validate a CTAS target and return a write handle with
        ``"mode": "create"`` (ConnectorMetadata.beginCreateTable
        analog). Side-effect free like begin_insert — the table only
        comes into existence at finish_write."""
        raise NotImplementedError(f"{type(self).__name__} is read-only")

    def write_sink(self, handle: dict, ctx: dict | None = None) -> "WriteSink":
        """Open a per-task sink for the handle (ConnectorPageSink
        analog). ``ctx`` carries the writing task's identity
        ``{"epoch", "task", "attempt"}`` so staged artifacts of
        distinct (speculated) attempts never collide."""
        raise NotImplementedError(f"{type(self).__name__} is read-only")

    def finish_write(
        self, handle: dict, fragments: list[str], token: str = "",
    ) -> int:
        """Commit the fragments of the winning writer attempts in one
        atomic step (ConnectorMetadata.finishInsert/finishCreateTable
        analog). ``token`` identifies the committing query epoch;
        implementations MUST be idempotent in it — a coordinator that
        crashed between commit and acknowledgment replays the same
        token and must observe the already-committed result, not a
        double apply. Returns total rows written."""
        raise NotImplementedError(f"{type(self).__name__} is read-only")

    def abort_write(self, handle: dict, token: str = ""):
        """Discard staged artifacts of a dead epoch (QUERY-tier retry
        or terminal failure). Best-effort; never raises."""

    def table_version(self, schema: str, table: str) -> int:
        """Monotonic write version (0 = versioning unsupported): DML
        reads it before evaluating its row mask and passes it back as
        ``expected_version`` so a concurrent write turns into a loud
        conflict instead of a misaligned positional mask."""
        return 0

    def delete_rows(
        self, schema: str, table: str, keep, expected_version: int = 0
    ) -> int:
        """Row-level DELETE: keep[i] marks surviving rows (table
        order). Returns deleted count (MergeWriterOperator analog)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support DELETE"
        )

    def update_rows(
        self, schema: str, table: str, columns: dict, mask,
        expected_version: int = 0,
    ) -> int:
        """Row-level UPDATE: overwrite ``columns`` (name ->
        (values, valid|None), full-length in table order) where
        mask[i]. Returns updated count."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support UPDATE"
        )


class WriteSink:
    """Per-task write sink (SPI/connector/ConnectorPageSink.java
    analog): the TableWriter operator appends host storage columns,
    then calls finish() exactly once to seal the task's output into
    *fragments* — opaque JSON strings that ride the exchange fabric to
    TableFinish, which hands the winning attempts' fragment set to
    ``Connector.finish_write``. Nothing a sink does is visible to
    readers until that commit.

    ``buffered_bytes`` is the sink's current host-memory footprint;
    the operator accounts its deltas against the task MemoryContext so
    buffered writes obey query_max_memory_per_node."""

    def __init__(self, handle: dict):
        self.handle = handle
        self.rows_written = 0
        self.bytes_written = 0
        self.files_written = 0
        self.buffered_bytes = 0

    def append(self, columns: dict, n_rows: int):
        """Buffer one page; ``columns`` maps column name ->
        (values, valid|None) host storage arrays, in handle order."""
        raise NotImplementedError

    def finish(self) -> list[str]:
        """Seal the sink: flush + fsync buffered data, return the
        fragment manifest (list of JSON strings)."""
        raise NotImplementedError

    def abort(self):
        """Drop buffered state (task failed mid-write). Best-effort."""
        self.buffered_bytes = 0


def handle_table_schema(handle: dict) -> TableSchema:
    """Reconstruct the target TableSchema from a write handle's
    JSON-safe ``columns`` list."""
    return TableSchema(
        handle["table"],
        [(c, T.type_from_name(t)) for c, t in handle["columns"]],
    )


@dataclass
class Catalog:
    name: str
    connector: Connector
    properties: dict = field(default_factory=dict)


# ---- host storage codec ----------------------------------------------------
# Python result rows -> the storage-form (values, valid) columns every
# write surface shares: the memory connector's insert, both WriteSink
# implementations, and the engine's host-side VALUES path. Lives here
# (not engine.py) so exec/write.py can use it without a circular import.

def rows_to_columns(ts: TableSchema, names: list[str], rows: list) -> dict:
    """Python result rows -> host storage columns (values, valid)."""
    out = {}
    for i, (c, t) in enumerate(zip(names, [ts.column_type(n) for n in names])):
        raw = [r[i] for r in rows]
        valid = np.array([v is not None for v in raw], dtype=bool)
        if isinstance(t, T.ArrayType):
            vals = np.empty(len(raw), dtype=object)
            for j, v in enumerate(raw):
                vals[j] = None if v is None else [
                    _elem_storage(x, t.element) for x in v
                ]
        elif isinstance(t, T.MapType):
            vals = np.empty(len(raw), dtype=object)
            for j, v in enumerate(raw):
                vals[j] = None if v is None else [
                    (_elem_storage(k, t.key),
                     None if x is None else _elem_storage(x, t.value))
                    for k, x in (
                        v.items() if isinstance(v, dict) else v
                    )
                ]
        elif isinstance(t, T.RowType):
            vals = np.empty(len(raw), dtype=object)
            for j, v in enumerate(raw):
                vals[j] = None if v is None else tuple(
                    None if x is None else _elem_storage(x, ft)
                    for x, (_fn, ft) in zip(v, t.fields)
                )
        elif isinstance(t, T.VarcharType):
            vals = np.array(
                ["" if v is None else str(v) for v in raw], dtype=object
            )
        elif isinstance(t, T.DecimalType):
            vals = np.array(
                [
                    0 if v is None else to_unscaled(v, t.scale)
                    for v in raw
                ],
                dtype=np.int64,
            )
        elif isinstance(t, T.DateType):
            vals = np.array(
                [
                    0 if v is None else (
                        T.parse_date(v) if isinstance(v, str) else int(v)
                    )
                    for v in raw
                ],
                dtype=t.np_dtype,
            )
        elif isinstance(t, T.TimestampType):
            vals = np.array(
                [
                    0 if v is None else (
                        T.parse_timestamp(v) if isinstance(v, str) else int(v)
                    )
                    for v in raw
                ],
                dtype=t.np_dtype,
            )
        else:
            vals = np.array(
                [0 if v is None else v for v in raw], dtype=t.np_dtype
            )
        out[c] = (vals, None if valid.all() else valid)
    return out


def _elem_storage(v, t):
    """One array ELEMENT -> the element type's storage form (mirrors
    the scalar branches of rows_to_columns: days for dates, unscaled
    ints for decimals, micros for timestamps)."""
    if isinstance(t, T.DecimalType):
        return to_unscaled(v, t.scale)
    if isinstance(t, T.DateType):
        return T.parse_date(v) if isinstance(v, str) else int(v)
    if isinstance(t, T.TimestampType):
        return T.parse_timestamp(v) if isinstance(v, str) else int(v)
    if isinstance(t, T.VarcharType):
        return str(v)
    return v


def to_unscaled(v, scale: int) -> int:
    from decimal import Decimal

    if isinstance(v, Decimal):
        return int(v.scaleb(scale))
    if isinstance(v, int):
        return v * 10**scale
    if isinstance(v, str):
        return int(Decimal(v).scaleb(scale))
    return round(float(v) * 10**scale)
