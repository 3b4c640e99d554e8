"""Parquet files connector: out-of-core columnar storage scans.

The analog of the reference's Hive-style file connectors sitting on
lib/trino-parquet (ParquetReader,
lib/trino-parquet/.../reader/ParquetReader.java:85). Two layouts are
exposed as catalog tables:

- ``root/<schema>/<table>.parquet`` — a single file (legacy layout);
- ``root/<schema>/<table>/<key>=<value>/.../*.parquet`` — a Hive-style
  partitioned directory tree; the ``key=value`` path segments become
  synthesized partition columns appended to the file schema.

A per-table *manifest* (file list + per-row-group footer stats, global
row offsets) is built once from metadata only — no data page is
touched. The manifest defines a global row order (files sorted by
relative path), so a ``Split`` stays a plain ``(start, count)`` row
range and the whole engine's split plumbing (serde, fleet binding,
streamed chunking) works unchanged; the connector maps any row range
back to the covering row groups at read time.

Pushdown happens at three levels, mirroring the reference:
- projection: only requested columns are decoded (ParquetReader column
  projection);
- partition pruning: ``key=value`` directories disjoint with a column
  domain are skipped without opening any file (HivePartitionManager);
- row-group pruning: footer min/max statistics disjoint with a domain
  skip the row group (TupleDomain → ParquetPredicate stripe pruning).

Nulls become validity masks, short decimals unscaled int64, decimals
with precision > 18 the engine's two-limb ``[n, 2]`` int64 layout,
dates int32 days, timestamps int64 micros — the device page layout.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

import numpy as np

from trino_tpu import telemetry
from trino_tpu import types as T
from trino_tpu.connectors.base import (
    ColumnStats, Connector, Split, TableSchema, TableStats, WriteSink,
)

__all__ = ["ParquetConnector", "write_parquet_table"]

# Arrow's default allocator (mimalloc in pyarrow 25.0.0) segfaults
# inside ``ParquetFile.__init__`` when a worker's task threads read
# under CPU contention in a process that also hosts XLA — reproducible
# with tests/test_storage_scan.py's fleet tests beside 12 busy cores:
# SIGSEGV at libarrow+0x16f8b89, both workers die, the query fails with
# "no live workers remain". The system allocator does not. Arrow reads
# the variable when libarrow loads, and every pyarrow import in this
# package is function-local, so setting it here is early enough;
# ``pyarrow.set_memory_pool`` later is not (libarrow's own default pool
# stays mimalloc).
os.environ.setdefault("ARROW_DEFAULT_MEMORY_POOL", "system")


def _arrow():
    import pyarrow
    import pyarrow.parquet as pq

    return pyarrow, pq


def _type_from_arrow(t) -> T.DataType:
    import pyarrow as pa

    if pa.types.is_boolean(t):
        return T.BOOLEAN
    if pa.types.is_int8(t):
        return T.TINYINT
    if pa.types.is_int16(t):
        return T.SMALLINT
    if pa.types.is_int32(t):
        return T.INTEGER
    if pa.types.is_int64(t):
        return T.BIGINT
    if pa.types.is_float32(t):
        return T.REAL
    if pa.types.is_float64(t):
        return T.DOUBLE
    if pa.types.is_decimal(t):
        # precision > 18 maps onto the engine's two-limb decimal(38)
        # host layout; DecimalType itself validates precision <= 38
        return T.DecimalType(t.precision, t.scale)
    if pa.types.is_date32(t):
        return T.DATE
    if pa.types.is_timestamp(t):
        if t.tz is not None:
            raise NotImplementedError(
                "timestamp with time zone is not supported yet"
            )
        return T.TIMESTAMP
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return T.VARCHAR
    raise NotImplementedError(f"parquet type {t}")


@dataclass
class _RowGroup:
    """One row group of one file, addressed in GLOBAL row order."""

    index: int          #: row-group index within its file
    start: int          #: global row offset
    count: int
    size_bytes: int
    #: column -> (lo, hi) in storage domain, footer min/max only
    stats: dict = field(default_factory=dict)


@dataclass
class _FileEntry:
    path: str
    start: int          #: global row offset of the file's first row
    count: int
    #: partition column -> typed value parsed from key=value segments
    partition: dict = field(default_factory=dict)
    row_groups: list = field(default_factory=list)


@dataclass
class _Manifest:
    files: list
    row_count: int
    #: [(name, DataType)] for synthesized partition columns
    partition_cols: list
    total_bytes: int
    rowgroups_total: int


class ParquetConnector(Connector):
    #: scan()/splits() accept ColumnDomains and prune partitions +
    #: rowgroups by footer statistics (ParquetReader's predicate
    #: pushdown, lib/trino-parquet/.../reader/ParquetReader.java:85)
    supports_domains = True

    #: scans can be iterated split-by-split without materializing the
    #: table — the executor may route through exec/stream_scan.py
    streamable = True

    def __init__(self, root: str, split_target_bytes: int = 64 << 20):
        self.root = root
        #: coalescing ceiling for splits() (Hive max-split-size analog)
        self.split_target_bytes = split_target_bytes
        self._schema_cache: dict[tuple[str, str], TableSchema] = {}
        self._manifest_cache: dict[tuple[str, str], _Manifest] = {}
        #: metrics of the LAST pruned scan / split enumeration (tests +
        #: EXPLAIN ANALYZE — the connector Metrics SPI analog,
        #: SPI/metrics/Metrics.java)
        self.scan_metrics: dict = {}

    def cache_fingerprint(self):
        """``(ident, content)`` for the cross-query caches (cache.py):
        the absolute root path names the data — two connector instances
        over the same files share cache entries — and the content
        digest (relative path + size + mtime_ns of every parquet file)
        busts them when anything on disk is rewritten out-of-band."""
        import hashlib

        root = os.path.abspath(self.root)
        h = hashlib.blake2b(digest_size=12)
        try:
            for dirpath, dirnames, filenames in os.walk(root):
                # uncommitted staging epochs are invisible to readers
                # and must not bust reader caches while a CTAS runs
                dirnames[:] = [
                    d for d in dirnames if not d.startswith("_tmp")
                ]
                dirnames.sort()
                for fn in sorted(filenames):
                    if not fn.endswith(".parquet"):
                        continue
                    p = os.path.join(dirpath, fn)
                    st = os.stat(p)
                    rel = os.path.relpath(p, root)
                    h.update(
                        f"{rel}:{st.st_size}:{st.st_mtime_ns};".encode()
                    )
        except OSError:
            return None  # unreadable root: per-instance isolation
        return f"parquet:{root}", h.hexdigest()

    def _file_path(self, schema: str, table: str) -> str:
        return os.path.join(self.root, schema, f"{table}.parquet")

    def _dir_path(self, schema: str, table: str) -> str:
        return os.path.join(self.root, schema, table)

    # ---- metadata --------------------------------------------------------

    def list_schemas(self) -> list[str]:
        if not os.path.isdir(self.root):
            return []
        return sorted(
            d for d in os.listdir(self.root)
            if os.path.isdir(os.path.join(self.root, d))
        )

    def list_tables(self, schema: str) -> list[str]:
        d = os.path.join(self.root, schema)
        if not os.path.isdir(d):
            return []
        out = set()
        for f in os.listdir(d):
            if f.endswith(".parquet"):
                out.add(f[:-8])
            elif os.path.isdir(os.path.join(d, f)) and not f.startswith(
                "_tmp"
            ):
                # _tmp_{token} staging epochs are not tables
                out.add(f)
        return sorted(out)

    def invalidate(self, schema: str | None = None, table: str | None = None):
        """Drop cached manifests/schemas (after an external write)."""
        if schema is None:
            self._schema_cache.clear()
            self._manifest_cache.clear()
        else:
            self._schema_cache.pop((schema, table), None)
            self._manifest_cache.pop((schema, table), None)

    def _manifest(self, schema: str, table: str) -> _Manifest:
        key = (schema, table)
        m = self._manifest_cache.get(key)
        if m is None:
            m = self._build_manifest(schema, table)
            self._manifest_cache[key] = m
        return m

    def _data_files(self, schema: str, table: str) -> list[str]:
        """Data file paths in global row order (sorted relative path)."""
        single = self._file_path(schema, table)
        if os.path.isfile(single):
            return [single]
        d = self._dir_path(schema, table)
        if not os.path.isdir(d):
            raise FileNotFoundError(single)
        found = []
        for base, _dirs, names in os.walk(d):
            for n in names:
                if n.endswith(".parquet"):
                    found.append(os.path.join(base, n))
        if not found:
            raise FileNotFoundError(f"no parquet files under {d}")
        return sorted(found)

    def _build_manifest(self, schema: str, table: str) -> _Manifest:
        _, pq = _arrow()
        paths = self._data_files(schema, table)
        d = self._dir_path(schema, table)
        # partition keys from key=value path segments; value type is
        # BIGINT only when EVERY file's value parses as int
        raw_parts: list[dict[str, str]] = []
        for p in paths:
            parts = {}
            rel = os.path.relpath(os.path.dirname(p), d)
            if rel != "." and not os.path.isfile(
                self._file_path(schema, table)
            ):
                for seg in rel.split(os.sep):
                    if "=" in seg:
                        k, _, v = seg.partition("=")
                        parts[k] = v
            raw_parts.append(parts)
        pkeys = list(dict.fromkeys(k for rp in raw_parts for k in rp))
        ptypes = {}
        for k in pkeys:
            vals = [rp.get(k) for rp in raw_parts]
            if any(v is None for v in vals):
                raise ValueError(
                    f"partition key {k!r} missing from some files of "
                    f"{schema}.{table}"
                )
            try:
                [int(v) for v in vals]
                ptypes[k] = T.BIGINT
            except ValueError:
                ptypes[k] = T.VARCHAR
        base_schema = self._file_table_schema(schema, table, paths[0])
        files = []
        start = 0
        total_bytes = 0
        rg_total = 0
        for p, rp in zip(paths, raw_parts):
            md = pq.ParquetFile(p).metadata
            part = {
                k: (int(rp[k]) if ptypes[k] is T.BIGINT else rp[k])
                for k in pkeys
            }
            fe = _FileEntry(p, start, md.num_rows, part)
            name_to_idx = {
                md.row_group(0).column(j).path_in_schema: j
                for j in range(md.row_group(0).num_columns)
            } if md.num_row_groups else {}
            for i in range(md.num_row_groups):
                rg = md.row_group(i)
                stats = {}
                for cname, j in name_to_idx.items():
                    st = rg.column(j).statistics
                    if st is None or not st.has_min_max:
                        continue
                    try:
                        t = base_schema.column_type(cname)
                    except KeyError:
                        continue
                    stats[cname] = (
                        _stat_to_storage(st.min, t),
                        _stat_to_storage(st.max, t),
                    )
                # partition values are exact single-value bounds
                for k, v in part.items():
                    stats[k] = (v, v)
                nbytes = rg.total_byte_size
                fe.row_groups.append(
                    _RowGroup(i, start, rg.num_rows, nbytes, stats)
                )
                start += rg.num_rows
                total_bytes += nbytes
                rg_total += 1
            files.append(fe)
        return _Manifest(
            files, start, [(k, ptypes[k]) for k in pkeys],
            total_bytes, rg_total,
        )

    def _file_table_schema(
        self, schema: str, table: str, path: str
    ) -> TableSchema:
        _, pq = _arrow()
        meta = pq.read_schema(path)
        return TableSchema(table, [
            (name, _type_from_arrow(meta.field(name).type))
            for name in meta.names
        ])

    def table_schema(self, schema: str, table: str) -> TableSchema:
        key = (schema, table)
        if key not in self._schema_cache:
            m = self._manifest(schema, table)
            ts = self._file_table_schema(schema, table, m.files[0].path)
            cols = list(ts.columns) + [
                (k, t) for k, t in m.partition_cols
                if k not in ts.column_names
            ]
            self._schema_cache[key] = TableSchema(table, cols)
        return self._schema_cache[key]

    def row_count(self, schema: str, table: str) -> int:
        return self._manifest(schema, table).row_count

    def table_stats(self, schema: str, table: str) -> TableStats:
        """Row count + exact per-column min/max merged from footers (no
        data pages touched) — feeds join ordering and df_range_keep."""
        m = self._manifest(schema, table)
        merged: dict[str, list] = {}
        counted: dict[str, int] = {}
        for fe in m.files:
            for rg in fe.row_groups:
                for c, (lo, hi) in rg.stats.items():
                    if lo is None or hi is None or isinstance(lo, str):
                        continue
                    cur = merged.get(c)
                    if cur is None:
                        merged[c] = [lo, hi]
                    else:
                        cur[0] = min(cur[0], lo)
                        cur[1] = max(cur[1], hi)
                    counted[c] = counted.get(c, 0) + rg.count
        cols = {
            c: ColumnStats(lo=v[0], hi=v[1])
            for c, v in merged.items()
            # only exact bounds: every row group must have reported
            if counted.get(c, 0) == m.row_count
        }
        return TableStats(float(m.row_count), cols)

    # ---- distributed write (TableWriter subsystem) -----------------------
    #
    # Writers stage row-group-sized part files under
    # ``root/schema/_tmp_{token}/table/[key=value/...]`` (a SIBLING of
    # the table dir, so readers never walk uncommitted data); commit
    # verifies each fragment's CRC, atomically renames winners into the
    # Hive-style table tree, records ``_manifest.json`` (the idempotent
    # commit marker), removes the whole staging epoch (loser-attempt
    # orphans included) and invalidates cached metadata so splits()/
    # table_stats see the new data immediately.

    def _staging_dir(self, schema: str, table: str, token: str) -> str:
        return os.path.join(
            self.root, schema, f"_tmp_{token or 'local'}", table
        )

    def begin_insert(self, schema: str, table: str) -> dict:
        ts = self.table_schema(schema, table)  # raises if missing
        m = self._manifest(schema, table)
        return {
            "schema": schema, "table": table, "mode": "insert",
            "columns": [[c, str(t)] for c, t in ts.columns],
            "partition_by": [k for k, _t in m.partition_cols],
            "row_group_size": None,
        }

    def begin_create(
        self, schema: str, table: str, table_schema: TableSchema,
        partition_by=None, properties=None,
    ) -> dict:
        partition_by = list(partition_by or [])
        for k in partition_by:
            t = table_schema.column_type(k)  # KeyError if unknown
            if not (t.is_integer or isinstance(t, T.VarcharType)):
                raise ValueError(
                    f"partition column {k!r} must be integer or varchar"
                )
        rgs = (properties or {}).get("row_group_size")
        return {
            "schema": schema, "table": table, "mode": "create",
            "columns": [[c, str(t)] for c, t in table_schema.columns],
            "partition_by": partition_by,
            "row_group_size": None if rgs is None else int(rgs),
        }

    def write_sink(self, handle: dict, ctx: dict | None = None):
        return _ParquetSink(self.root, handle, ctx)

    def finish_write(
        self, handle: dict, fragments: list[str], token: str = "",
    ) -> int:
        import json
        import shutil
        import zlib

        schema, table = handle["schema"], handle["table"]
        tdir = self._dir_path(schema, table)
        staging = self._staging_dir(schema, table, token)
        manifest_path = os.path.join(tdir, "_manifest.json")
        prior = None
        if os.path.isfile(manifest_path):
            with open(manifest_path) as f:
                prior = json.load(f)
            if token and prior.get("token") == token:
                # replayed commit (coordinator crashed after commit,
                # before the client saw the result): already applied
                shutil.rmtree(
                    os.path.dirname(staging), ignore_errors=True
                )
                return int(prior.get("rows", 0))
        single = self._file_path(schema, table)
        if handle["mode"] == "insert" and os.path.isfile(single):
            # legacy single-file table gains part files: fold the
            # original file into the directory layout first
            os.makedirs(tdir, exist_ok=True)
            os.replace(
                single, os.path.join(tdir, "part-00000-legacy.parquet")
            )
        frags = [json.loads(s) for s in fragments]
        total_rows = 0
        entries = list(prior["files"]) if prior else []
        # a fragment path already in the manifest belongs to COMMITTED
        # data — renaming over it would silently destroy rows (part
        # names carry the epoch precisely so this cannot happen; treat
        # a collision as corruption, not as an update)
        dup = {e["path"] for e in entries} & {fr["path"] for fr in frags}
        if dup:
            raise IOError(
                f"write fragments collide with committed part files "
                f"{sorted(dup)}; refusing to overwrite"
            )
        touched_dirs = set()
        for fr in frags:
            staged = os.path.join(staging, fr["path"])
            dest = os.path.join(tdir, fr["path"])
            if not os.path.isfile(staged):
                if os.path.isfile(dest) and os.path.getsize(dest) == int(
                    fr["bytes"]
                ):
                    # crashed between this rename and the manifest
                    # write on a previous commit attempt
                    total_rows += int(fr["rows"])
                    entries.append(_manifest_entry(fr))
                    continue
                raise FileNotFoundError(
                    f"staged write fragment missing: {staged}"
                )
            with open(staged, "rb") as f:
                crc = zlib.crc32(f.read()) & 0xFFFFFFFF
            if crc != int(fr["crc"]):
                raise IOError(
                    f"write fragment CRC mismatch for {fr['path']}: "
                    f"staged file is corrupt, refusing to commit"
                )
            os.makedirs(os.path.dirname(dest), exist_ok=True)
            os.replace(staged, dest)
            touched_dirs.add(os.path.dirname(dest))
            total_rows += int(fr["rows"])
            entries.append(_manifest_entry(fr))
        for d in sorted(touched_dirs):
            _fsync_dir(d)
        os.makedirs(tdir, exist_ok=True)
        if handle["mode"] == "create" and not frags:
            # empty CTAS: the table must still be readable, so write
            # one zero-row part file carrying the schema
            from trino_tpu.connectors.base import (
                handle_table_schema, rows_to_columns,
            )

            ts = handle_table_schema(handle)
            fs = TableSchema(table, [
                (c, t) for c, t in ts.columns
                if c not in (handle.get("partition_by") or [])
            ])
            empty = rows_to_columns(fs, fs.column_names, [])
            _write_file(
                os.path.join(tdir, "part-empty-0000.parquet"),
                fs, empty, fsync=True,
            )
        tmp = manifest_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(
                {
                    "token": token,
                    "rows": total_rows,
                    "files": entries,
                },
                f,
            )
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, manifest_path)
        _fsync_dir(tdir)
        # the epoch's staging root also holds losing speculated
        # attempts' part files — drop them all (zero orphans)
        shutil.rmtree(os.path.dirname(staging), ignore_errors=True)
        self.invalidate(schema, table)
        return total_rows

    def abort_write(self, handle: dict, token: str = ""):
        import shutil

        staging = self._staging_dir(handle["schema"], handle["table"], token)
        shutil.rmtree(os.path.dirname(staging), ignore_errors=True)

    # ---- splits ----------------------------------------------------------

    def splits(
        self, schema: str, table: str, target_splits: int,
        domains: dict | None = None,
    ) -> list[Split]:
        """One Split per surviving row group, coalesced to a byte
        target (the Hive split model: HiveSplitSource + max-split-size
        coalescing). ``domains`` prunes partitions and row groups from
        footer stats before any split exists; never coalesces across a
        pruned or non-adjacent row group, so a split's row range reads
        back exactly its surviving row groups."""
        m = self._manifest(schema, table)
        domains = domains or {}
        pruned_partitions: set[tuple] = set()
        all_partitions: set[tuple] = set()
        survivors: list[_RowGroup] = []
        rg_pruned = 0
        live_bytes = 0
        for fe in m.files:
            pkey = tuple(sorted(fe.partition.items()))
            if fe.partition:
                all_partitions.add(pkey)
            if fe.partition and any(
                dom is not None and dom.disjoint(
                    fe.partition[k], fe.partition[k]
                )
                for k, dom in domains.items() if k in fe.partition
            ):
                pruned_partitions.add(pkey)
                continue
            for rg in fe.row_groups:
                if any(
                    dom is not None and c in rg.stats
                    and dom.disjoint(*rg.stats[c])
                    for c, dom in domains.items()
                ):
                    rg_pruned += 1
                    continue
                survivors.append(rg)
                live_bytes += rg.size_bytes
        target_splits = max(1, target_splits)
        target_bytes = min(
            self.split_target_bytes,
            max(1, -(-live_bytes // target_splits)),
        )
        out: list[Split] = []
        cur: list[_RowGroup] = []
        cur_bytes = 0

        def _flush():
            nonlocal cur, cur_bytes
            if not cur:
                return
            stats: dict[str, list] = {}
            # merge bounds; a column must appear in EVERY member to
            # stay (a missing footer stat means unknown, not empty)
            common = set(cur[0].stats)
            for rg in cur[1:]:
                common &= set(rg.stats)
            for c in common:
                los = [rg.stats[c][0] for rg in cur]
                his = [rg.stats[c][1] for rg in cur]
                if any(v is None for v in los + his):
                    continue
                try:
                    stats[c] = [min(los), max(his)]
                except TypeError:
                    continue
            out.append(Split(
                table, cur[0].start, sum(rg.count for rg in cur),
                size_bytes=cur_bytes,
                stats=tuple(
                    (c, lo, hi) for c, (lo, hi) in sorted(stats.items())
                ),
            ))
            cur, cur_bytes = [], 0

        for rg in survivors:
            adjacent = bool(cur) and cur[-1].start + cur[-1].count == rg.start
            if cur and (
                not adjacent or cur_bytes + rg.size_bytes > target_bytes
            ):
                _flush()
            cur.append(rg)
            cur_bytes += rg.size_bytes
        _flush()
        self.scan_metrics = {
            "rowgroups_total": m.rowgroups_total,
            "rowgroups_read": len(survivors),
            "rowgroups_pruned": rg_pruned,
            "partitions_total": len(all_partitions),
            "partitions_pruned": len(pruned_partitions),
            "splits": len(out),
        }
        telemetry.SCAN_ROWGROUPS_TOTAL.inc(m.rowgroups_total, table=table)
        telemetry.SCAN_ROWGROUPS_PRUNED.inc(rg_pruned, table=table)
        telemetry.SCAN_PARTITIONS_PRUNED.inc(
            len(pruned_partitions), table=table
        )
        return out or [Split(table, 0, 0)]

    # ---- scan ------------------------------------------------------------

    def scan(
        self, schema: str, table: str, columns: list[str],
        split: Split | None = None, domains=None,
    ):
        """Produce host arrays for the requested columns.

        ``split`` may be ANY global row range — not just one produced
        by splits(): the streamed-chunk reader slices uniform chunks.
        Only row groups overlapping the range are decoded; ``domains``
        additionally skips stats-disjoint row groups (pruning-safe: the
        engine re-applies the full filter)."""
        m = self._manifest(schema, table)
        ts = self.table_schema(schema, table)
        lo = 0 if split is None else split.start
        hi = m.row_count if split is None else min(
            m.row_count, split.start + split.count
        )
        domains = domains or {}
        pcols = {k for k, _ in m.partition_cols}
        file_cols = [c for c in columns if c not in pcols]
        pieces: list[tuple[int, dict]] = []  # (n_rows, col -> host)
        rg_total = 0
        rg_read = 0
        parts_pruned: set[tuple] = set()
        bytes_read = 0
        for fe in m.files:
            if fe.start >= hi or fe.start + fe.count <= lo:
                continue
            rg_total += len(fe.row_groups)
            if fe.partition and any(
                dom is not None and dom.disjoint(
                    fe.partition[k], fe.partition[k]
                )
                for k, dom in domains.items() if k in fe.partition
            ):
                parts_pruned.add(tuple(sorted(fe.partition.items())))
                continue
            keep = []
            for rg in fe.row_groups:
                if rg.start >= hi or rg.start + rg.count <= lo:
                    continue
                if any(
                    dom is not None and c in rg.stats
                    and dom.disjoint(*rg.stats[c])
                    for c, dom in domains.items()
                ):
                    continue
                keep.append(rg)
            if not keep:
                continue
            rg_read += len(keep)
            bytes_read += sum(rg.size_bytes for rg in keep)
            n, cols = self._read_file_rowgroups(fe, keep, file_cols, ts, lo, hi)
            if n == 0:
                continue
            for k, t in m.partition_cols:
                if k in columns and k not in cols:
                    cols[k] = _const_column(fe.partition[k], t, n)
            pieces.append((n, cols))
        telemetry.SCAN_BYTES_READ.inc(bytes_read, table=table)
        if split is None and domains:
            # whole-table pruned scan: report connector metrics the way
            # the legacy single-file path always did
            self.scan_metrics = {
                "rowgroups_total": rg_total,
                "rowgroups_read": rg_read,
                "rowgroups_pruned": rg_total - rg_read
                - sum(
                    len(fe.row_groups) for fe in m.files
                    if tuple(sorted(fe.partition.items())) in parts_pruned
                ),
                "partitions_pruned": len(parts_pruned),
            }
            telemetry.SCAN_ROWGROUPS_TOTAL.inc(rg_total, table=table)
            telemetry.SCAN_ROWGROUPS_PRUNED.inc(
                self.scan_metrics["rowgroups_pruned"], table=table
            )
            telemetry.SCAN_PARTITIONS_PRUNED.inc(
                len(parts_pruned), table=table
            )
        return _concat_pieces(pieces, columns, ts)

    def _read_file_rowgroups(
        self, fe: _FileEntry, keep: list, file_cols: list,
        ts: TableSchema, lo: int, hi: int,
    ):
        """Decode the kept row groups of one file, sliced to the global
        [lo, hi) range; returns (n_rows, col -> host arrays)."""
        _, pq = _arrow()
        # kept row groups are contiguous-or-not; read them as one arrow
        # table (global offsets of each are known, so edge-slice per
        # contiguous run)
        runs: list[list] = []
        for rg in keep:
            if runs and runs[-1][-1].index + 1 == rg.index and (
                runs[-1][-1].start + runs[-1][-1].count == rg.start
            ):
                runs[-1].append(rg)
            else:
                runs.append([rg])
        pf = pq.ParquetFile(fe.path)
        n_total = 0
        per_col: dict[str, list] = {c: [] for c in file_cols}
        for run in runs:
            run_start = run[0].start
            run_count = sum(rg.count for rg in run)
            off = max(0, lo - run_start)
            take = min(run_start + run_count, hi) - max(run_start, lo)
            if take <= 0:
                continue
            if file_cols:
                tbl = pf.read_row_groups(
                    [rg.index for rg in run], columns=list(file_cols)
                )
                if off or take != run_count:
                    tbl = tbl.slice(off, take)
                for c in file_cols:
                    per_col[c].append(tbl.column(c))
            n_total += take
        out = {}
        for c in file_cols:
            arrs = per_col[c]
            if not arrs:
                continue
            out[c] = _to_host(
                _combine_arrow(arrs), ts.column_type(c)
            )
        return n_total, out

    def _read_pruned(self, schema, table, columns, domains):
        """Back-compat shim: whole-table domain-pruned read."""
        return self.scan(schema, table, columns, domains=domains)


def _combine_arrow(arrs):
    """Chunked/plain arrow arrays -> one contiguous Array."""
    import pyarrow as pa

    chunks = []
    for a in arrs:
        if isinstance(a, pa.ChunkedArray):
            chunks.extend(a.chunks)
        else:
            chunks.append(a)
    if len(chunks) == 1:
        return chunks[0]
    return pa.chunked_array(chunks).combine_chunks()


def _const_column(value, t: T.DataType, n: int):
    """Synthesize a partition column as n copies of its value."""
    if isinstance(t, T.VarcharType):
        out = np.empty(n, dtype=object)
        out[:] = value
        return out
    return np.full(n, value, dtype=t.np_dtype)


def _empty_host(t: T.DataType):
    if isinstance(t, T.VarcharType):
        return np.empty(0, dtype=object)
    if isinstance(t, T.DecimalType) and t.is_long:
        return np.empty((0, 2), dtype=np.int64)
    return np.empty(0, dtype=t.np_dtype)


def _concat_pieces(pieces, columns, ts: TableSchema):
    """Stitch per-file host fragments into one (values, valid|None)
    dict, preserving global row order (pieces arrive ordered)."""
    if not pieces:
        return {c: _empty_host(ts.column_type(c)) for c in columns}
    if len(pieces) == 1:
        n, cols = pieces[0]
        return {c: cols[c] for c in columns}
    out = {}
    for c in columns:
        vals_parts = []
        valid_parts = []
        any_null = False
        for n, cols in pieces:
            v = cols[c]
            if isinstance(v, tuple):
                vals, valid = v
                if valid is None:
                    valid = np.ones(len(vals), dtype=bool)
                else:
                    any_null = True
            else:
                vals, valid = v, np.ones(len(v), dtype=bool)
            vals_parts.append(vals)
            valid_parts.append(valid)
        vals = np.concatenate(vals_parts)
        if any_null:
            out[c] = (vals, np.concatenate(valid_parts))
        else:
            out[c] = vals
    return out


def _stat_to_storage(v, t: T.DataType):
    """Parquet footer statistic -> the engine's storage domain (days
    for dates, unscaled ints for decimals, micros for timestamps)."""
    import datetime
    import decimal

    if v is None:
        return None
    if isinstance(t, T.DateType) and isinstance(v, datetime.date):
        return (v - datetime.date(1970, 1, 1)).days
    if isinstance(t, T.TimestampType) and isinstance(v, datetime.datetime):
        epoch = datetime.datetime(1970, 1, 1)
        return int((v - epoch).total_seconds() * 1_000_000)
    if isinstance(t, T.DecimalType):
        if isinstance(v, decimal.Decimal):
            return int(v.scaleb(t.scale))
        return int(decimal.Decimal(str(v)).scaleb(t.scale))
    return v


def _to_host(arr, t: T.DataType):
    """Arrow array -> (values, valid|None) in the engine's host layout."""
    valid = None
    if arr.null_count:
        valid = np.asarray(arr.is_valid())
    if isinstance(t, T.VarcharType):
        vals = np.asarray(
            ["" if v is None else v for v in arr.to_pylist()], dtype=object
        )
    elif isinstance(t, T.DecimalType) and t.is_long:
        import pyarrow as pa

        # two-limb [n, 2] int64: hi = unscaled >> 32 (floor), lo = low
        # 32 bits — the engine's decimal(38) device layout
        unscaled = arr.cast(pa.decimal128(38, t.scale))
        vals = np.zeros((len(arr), 2), dtype=np.int64)
        for i, v in enumerate(unscaled.to_pylist()):
            if v is None:
                continue
            u = int(v.scaleb(t.scale))
            vals[i, 0] = u >> 32
            vals[i, 1] = u & 0xFFFFFFFF
    elif isinstance(t, T.DecimalType):
        import pyarrow as pa

        unscaled = arr.cast(pa.decimal128(38, t.scale))
        vals = np.asarray(
            [0 if v is None else int(v.scaleb(t.scale)) for v in
             unscaled.to_pylist()],
            dtype=np.int64,
        )
    elif isinstance(t, T.DateType):
        import pyarrow as pa

        vals = np.asarray(arr.cast(pa.int32()).fill_null(0))
    elif isinstance(t, T.TimestampType):
        import pyarrow as pa

        vals = np.asarray(
            # safe=False: truncate sub-microsecond units (ns files)
            # like the reference rather than raising
            arr.cast(pa.timestamp("us"), safe=False)
            .cast(pa.int64()).fill_null(0)
        )
    else:
        vals = np.asarray(arr.fill_null(0) if arr.null_count else arr)
    return vals if valid is None else (vals, valid)


def _columns_to_arrow(table_schema: TableSchema, columns: dict, sel=None):
    """Host columns -> (arrays, names) for the columns present in
    ``table_schema``, optionally row-selected by boolean mask ``sel``."""
    pa, _ = _arrow()
    arrays = []
    names = []
    for c, t in table_schema.columns:
        vals = columns[c]
        valid = None
        if isinstance(vals, tuple):
            vals, valid = vals
        vals = np.asarray(vals)
        if sel is not None:
            vals = vals[sel]
            valid = None if valid is None else np.asarray(valid)[sel]
        mask = None if valid is None else ~np.asarray(valid, dtype=bool)
        if isinstance(t, T.VarcharType):
            arr = pa.array(list(vals), type=pa.string(), mask=mask)
        elif isinstance(t, T.DecimalType):
            import decimal

            if t.is_long and vals.ndim == 2:
                # two-limb [n, 2] input: unscaled = hi * 2^32 + lo
                py = [
                    decimal.Decimal(
                        int(v[0]) * (1 << 32) + int(v[1])
                    ).scaleb(-t.scale)
                    for v in vals
                ]
            else:
                py = [
                    decimal.Decimal(int(v)).scaleb(-t.scale) for v in vals
                ]
            arr = pa.array(
                py, type=pa.decimal128(t.precision, t.scale), mask=mask
            )
        elif isinstance(t, T.DateType):
            arr = pa.array(
                np.asarray(vals, dtype=np.int32), type=pa.date32(), mask=mask
            )
        elif isinstance(t, T.TimestampType):
            arr = pa.array(
                np.asarray(vals, dtype=np.int64),
                type=pa.timestamp("us"), mask=mask,
            )
        else:
            arr = pa.array(vals, mask=mask)
        arrays.append(arr)
        names.append(c)
    return arrays, names


def _write_file(
    path: str, file_schema: TableSchema, columns: dict,
    row_group_size: int | None = None, sel=None, fsync: bool = False,
):
    """Encode host columns into ONE parquet file — the single encoder
    shared by the legacy export helper and the WriteSink path."""
    pa, pq = _arrow()
    kw = {} if row_group_size is None else {"row_group_size": row_group_size}
    arrays, names = _columns_to_arrow(file_schema, columns, sel=sel)
    pq.write_table(pa.Table.from_arrays(arrays, names=names), path, **kw)
    if fsync:
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)


class _ParquetSink(WriteSink):
    """Per-task parquet page sink: buffers rows per partition tuple
    and flushes part files under the staging epoch dir. Nothing lands
    in the table tree until ``ParquetConnector.finish_write`` renames
    the winning fragments in."""

    #: buffered rows per partition tuple that trigger a part-file
    #: flush (the "row-group-sized part files" unit; row_group_size,
    #: when set, additionally shapes row groups INSIDE a file)
    FLUSH_ROWS = 1 << 20

    def __init__(self, root: str, handle: dict, ctx: dict | None = None):
        super().__init__(handle)
        ctx = ctx or {}
        self.root = root
        self.epoch = str(ctx.get("epoch") or "local")
        self.task = str(ctx.get("task") or "t0")
        self.attempt = int(ctx.get("attempt") or 0)
        self.staging = os.path.join(
            root, handle["schema"], f"_tmp_{self.epoch}", handle["table"]
        )
        pb = list(handle.get("partition_by") or [])
        self.partition_by = pb
        cols = [(c, T.type_from_name(t)) for c, t in handle["columns"]]
        self.table_schema = TableSchema(handle["table"], cols)
        self.file_schema = TableSchema(
            handle["table"], [(c, t) for c, t in cols if c not in pb]
        )
        self.row_group_size = handle.get("row_group_size")
        #: partition tuple -> {col: ([values], valid list)}
        self._buf: dict[tuple, dict] = {}
        self._buf_rows: dict[tuple, int] = {}
        self._buf_bytes: dict[tuple, int] = {}
        self._seq = 0
        self._frags: list[dict] = []

    def append(self, columns: dict, n_rows: int):
        if not n_rows:
            return
        if self.partition_by:
            pvals = []
            for k in self.partition_by:
                vals, valid = columns[k]
                if valid is not None and not np.asarray(valid).all():
                    raise ValueError(
                        f"NULL value in partition column {k!r}"
                    )
                pvals.append(np.asarray(vals).tolist())
            keys = list(zip(*pvals))
        else:
            keys = [()] * n_rows
        for combo in dict.fromkeys(keys):
            sel = np.fromiter(
                (key == combo for key in keys), dtype=bool, count=n_rows
            )
            buf = self._buf.get(combo)
            if buf is None:
                buf = self._buf[combo] = {
                    c: ([], []) for c, _t in self.file_schema.columns
                }
                self._buf_rows[combo] = 0
                self._buf_bytes[combo] = 0
            k = int(sel.sum())
            for c, _t in self.file_schema.columns:
                vals, valid = columns[c]
                vals = np.asarray(vals)[sel]
                buf[c][0].extend(vals.tolist())
                buf[c][1].extend(
                    [True] * k if valid is None
                    else np.asarray(valid, dtype=bool)[sel].tolist()
                )
                b = _approx_col_bytes(vals)
                self._buf_bytes[combo] += b
                self.buffered_bytes += b
            self._buf_rows[combo] += k
            if self._buf_rows[combo] >= self.FLUSH_ROWS:
                self._flush(combo)
        self.rows_written += n_rows

    def _flush(self, combo: tuple):
        import zlib

        buf = self._buf.pop(combo)
        n = self._buf_rows.pop(combo)
        self.buffered_bytes = max(
            self.buffered_bytes - self._buf_bytes.pop(combo, 0), 0
        )
        if not n:
            return
        segs = [
            f"{k}={v}" for k, v in zip(self.partition_by, combo)
        ]
        for s in segs:
            if os.sep in s or s.startswith("_tmp"):
                raise ValueError(f"unsafe partition path segment {s!r}")
        d = os.path.join(self.staging, *segs)
        os.makedirs(d, exist_ok=True)
        # the epoch in the name keeps successive writes into one table
        # from colliding (same task ids every statement); task+attempt
        # keep speculated twins of one epoch apart
        name = (
            f"part-{self.epoch}-{self.task}-a{self.attempt}"
            f"-{self._seq:04d}.parquet"
        )
        self._seq += 1
        path = os.path.join(d, name)
        cols = {
            c: (buf[c][0], _valid_arr(buf[c][1]))
            for c, _t in self.file_schema.columns
        }
        _write_file(
            path, self.file_schema, cols,
            row_group_size=self.row_group_size, fsync=True,
        )
        with open(path, "rb") as f:
            data = f.read()
        crc = zlib.crc32(data) & 0xFFFFFFFF
        _, pq = _arrow()
        md = pq.ParquetFile(path).metadata
        stats = _footer_bounds(md, self.file_schema)
        self._frags.append({
            "path": os.path.join(*segs, name) if segs else name,
            "rows": n,
            "bytes": len(data),
            "crc": crc,
            "partition": dict(zip(self.partition_by, combo)),
            "stats": stats,
        })
        self.bytes_written += len(data)
        self.files_written += 1

    def finish(self) -> list[str]:
        import json

        for combo in list(self._buf):
            self._flush(combo)
        self.buffered_bytes = 0
        return [json.dumps(fr) for fr in self._frags]

    def abort(self):
        """Buffered pages drop here; already-staged part files are
        swept with the epoch dir by finish_write/abort_write."""
        self._buf.clear()
        self._buf_rows.clear()
        self.buffered_bytes = 0


def _valid_arr(flags: list):
    a = np.asarray(flags, dtype=bool)
    return None if a.all() else a


def _approx_col_bytes(vals: np.ndarray) -> int:
    if vals.dtype != object:
        return int(vals.nbytes)
    return sum(len(str(v)) + 8 for v in vals.tolist())


def _footer_bounds(md, file_schema: TableSchema) -> dict:
    """Merged per-column (lo, hi) storage-domain bounds from the
    footer of one written file (the fragment's stats payload)."""
    out: dict[str, list] = {}
    if not md.num_row_groups:
        return out
    name_to_idx = {
        md.row_group(0).column(j).path_in_schema: j
        for j in range(md.row_group(0).num_columns)
    }
    for i in range(md.num_row_groups):
        rg = md.row_group(i)
        for cname, j in name_to_idx.items():
            st = rg.column(j).statistics
            if st is None or not st.has_min_max:
                continue
            try:
                t = file_schema.column_type(cname)
            except KeyError:
                continue
            lo = _stat_to_storage(st.min, t)
            hi = _stat_to_storage(st.max, t)
            if isinstance(lo, bytes) or isinstance(hi, bytes):
                continue  # keep fragments JSON-safe
            cur = out.get(cname)
            if cur is None:
                out[cname] = [lo, hi]
            else:
                cur[0] = min(cur[0], lo)
                cur[1] = max(cur[1], hi)
    return out


def _manifest_entry(fr: dict) -> dict:
    return {
        "path": fr["path"], "rows": int(fr["rows"]),
        "bytes": int(fr["bytes"]), "crc": int(fr["crc"]),
    }


def _fsync_dir(path: str):
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def write_parquet_table(
    root: str, schema: str, table: str, table_schema: TableSchema,
    columns: dict, row_group_size: int | None = None,
    partition_by: list[str] | None = None,
):
    """Write host columns as parquet (the export half of the ingest
    path; the reference writes via ParquetWriter).

    Without ``partition_by``: one file ``root/schema/table.parquet``.
    With it: a Hive-style tree ``root/schema/table/<key>=<value>/
    part-*.parquet``, one file per distinct partition tuple, with the
    partition columns elided from the files (they live in the path).
    Both shapes route through the WriteSink encoder; the partitioned
    shape additionally exercises the stage-then-commit path, so every
    partitioned fixture in the tree is built by the same machinery a
    distributed CTAS uses."""
    if not partition_by:
        os.makedirs(os.path.join(root, schema), exist_ok=True)
        _write_file(
            os.path.join(root, schema, f"{table}.parquet"),
            table_schema, columns, row_group_size=row_group_size,
        )
        return
    conn = ParquetConnector(root)
    handle = conn.begin_create(
        schema, table, table_schema, partition_by=partition_by,
        properties=(
            None if row_group_size is None
            else {"row_group_size": row_group_size}
        ),
    )
    sink = conn.write_sink(
        handle, {"epoch": "bootstrap", "task": "t0", "attempt": 0}
    )
    norm = {}
    n = None
    for c, _t in table_schema.columns:
        v = columns[c]
        vals, valid = v if isinstance(v, tuple) else (v, None)
        vals = np.asarray(vals)
        n = len(vals) if n is None else n
        norm[c] = (vals, valid)
    sink.append(norm, n or 0)
    conn.finish_write(handle, sink.finish(), token="bootstrap")
