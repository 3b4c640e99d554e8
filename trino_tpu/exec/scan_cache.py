"""Process-wide device-resident scan-page cache.

The serving layer runs many concurrent queries in one process, and the
hot TPC tables they scan are identical. Historically each executor
owned its own ``_scan_cache`` dict, so every executor paid its own
host->HBM transfer for the same table — fine when one long-lived
executor served every statement, wasteful the moment several engines
coexist (a coordinator's embedded runner, per-group fleet planners,
test fixtures). This module hoists that storage to a single
process-wide cache, the analog of the reference's worker-shared memory
connector pages.

Keying is by *connector fingerprint* (cache.connector_fingerprint):
connectors that implement ``cache_fingerprint()`` — parquet, whose
ident is the root path and whose content digests footer sizes+mtimes —
share entries across connector INSTANCES over the same files, and an
out-of-band rewrite flips the content digest, dropping every stale
page at the next probe. Connectors without the hook get a per-instance
token, preserving the historical isolation contract (two TpchConnector
fixtures with independently mutated tables never share), with a weak
finalizer so dropping the last connector reference frees its device
pages.

DML invalidation routes here too: a write through ANY executor drops
the shared entry, so a concurrent reader re-scans instead of serving
pages observed before the write.

Hit/miss traffic surfaces as ``trino_scan_cache_{hits,misses}_total``.
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict

__all__ = ["ScanPageCache", "SplitBatchCache", "SHARED", "SHARED_SPLITS"]


def _fingerprint(connector) -> tuple[str, str]:
    from trino_tpu.cache import connector_fingerprint

    return connector_fingerprint(connector)


def column_nbytes(col) -> int:
    """Device bytes of one cached Column: its data and validity lanes."""
    nbytes = getattr(getattr(col, "data", None), "nbytes", 0) or 0
    valid = getattr(col, "valid", None)
    if valid is not None:
        nbytes += getattr(valid, "nbytes", 0) or 0
    return nbytes


class ScanPageCache:
    """connector fingerprint -> (schema, table) -> per-table page dict.

    The per-table dict is the same shape executors always used:
    column-cache-key -> device Column, ``""`` -> validity mask,
    ``"#rows"`` -> row count. Callers mutate it in place under the
    engine's execution serialization; this class only guards the
    *map* structure with its own lock so concurrent executors can
    resolve tables without racing the map.
    """

    def __init__(self):
        # reentrant: ``_drop_ident`` is a weakref finalizer, and the
        # collector can run it on THIS thread while ``table()`` holds
        # the lock (any allocation inside the critical section may
        # trigger a collection) — a plain Lock self-deadlocks there
        # (seen: tests/test_write_path.py hung in a full tier-1 run)
        self._lock = threading.RLock()
        #: ident -> [content, {(schema, table): page dict}]
        self._by_ident: dict[str, list] = {}
        #: idents with a registered instance finalizer
        self._watched: set[str] = set()

    def table(self, connector, schema: str, table: str) -> dict:
        """The live page dict for one table (created empty on first
        use). Records a hit when the table is already device-resident
        (mask present — columns may still be added lazily), a miss
        when this call created the entry."""
        from trino_tpu import telemetry

        ident, content = _fingerprint(connector)
        with self._lock:
            ent = self._by_ident.get(ident)
            if ent is not None and ent[0] != content:
                # on-disk content changed out-of-band: every page
                # under this ident was observed against old bytes
                ent = None
            if ent is None:
                ent = self._by_ident[ident] = [content, {}]
                if ident.startswith("id:") and ident not in self._watched:
                    # instance-keyed entries die with the connector
                    self._watched.add(ident)
                    weakref.finalize(connector, self._drop_ident, ident)
            tables = ent[1]
            cache = tables.get((schema, table))
            if cache is not None and "" in cache:
                telemetry.SCAN_CACHE_HITS.inc(table=table)
            else:
                telemetry.SCAN_CACHE_MISSES.inc(table=table)
            if cache is None:
                cache = tables[(schema, table)] = {}
            return cache

    def _drop_ident(self, ident: str) -> None:
        with self._lock:
            self._watched.discard(ident)
            self._by_ident.pop(ident, None)
        self.publish()

    def invalidate(self, connector, schema: str, table: str) -> None:
        """Drop one table's pages (after DML through any executor)."""
        ident, _content = _fingerprint(connector)
        with self._lock:
            ent = self._by_ident.get(ident)
            if ent is not None:
                ent[1].pop((schema, table), None)
        self.publish()

    def resident_tables(self, connector) -> list[tuple[str, str]]:
        """(schema, table) pairs currently device-resident for one
        connector (observability/tests)."""
        ident, content = _fingerprint(connector)
        with self._lock:
            ent = self._by_ident.get(ident)
            if ent is None or ent[0] != content:
                return []
            return [k for k, v in ent[1].items() if "" in v]

    def describe(self) -> list[dict]:
        """One object a resident whole-table page: schema, table, live
        rows, padded capacity, resident columns and their device bytes
        (data and validity lanes, and the live mask)."""
        out = []
        with self._lock:
            for _content, tables in self._by_ident.values():
                for (schema, table), cache in tables.items():
                    if "" not in cache:
                        continue
                    mask = cache[""]
                    columns = [
                        v for k, v in cache.items() if k not in ("", "#rows")
                    ]
                    out.append({
                        "schema": schema, "table": table,
                        "rows": int(cache.get("#rows", 0)),
                        "capacity": int(getattr(mask, "size", 0)),
                        "columns": len(columns),
                        "bytes": int(
                            (getattr(mask, "nbytes", 0) or 0)
                            + sum(column_nbytes(v) for v in columns)
                        ),
                    })
        return out

    def snapshot(self) -> dict:
        """entries/bytes across every resident table page dict
        (system.runtime.caches feed)."""
        tables = self.describe()
        return {"entries": len(tables),
                "bytes": sum(t["bytes"] for t in tables)}

    def publish(self) -> None:
        """Set the residency gauges from what is held now. Called where
        a table's page is stored (the scan that uploaded columns) or
        dropped; nothing polls."""
        from trino_tpu import telemetry

        with self._lock:  # two stores publish in the order they count
            snap = self.snapshot()
            telemetry.SCAN_CACHE_RESIDENT_BYTES.set(snap["bytes"])
            telemetry.SCAN_CACHE_RESIDENT_TABLES.set(snap["entries"])

    def clear(self) -> None:
        with self._lock:
            self._by_ident.clear()
        self.publish()


class SplitBatchCache:
    """Byte-bounded LRU for streamed-split host batches.

    Whole-table identity caching (above) is exactly wrong for
    out-of-core scans: pinning every page of an SF100 table would
    recreate the memory problem streaming exists to avoid. Streamed
    reads instead cache per-(fingerprint, schema, table, row-range,
    columns) host batches with an LRU bounded by total bytes, so a hot
    working set (dimension tables, re-scanned probe splits) stays warm
    while a single pass over a huge fact table churns through without
    accumulating. Fingerprint keying shares batches across connector
    instances over the same files; instance-keyed idents carry a weak
    finalizer that drops the connector's entries when it is collected —
    same isolation contract as ScanPageCache without pinning the
    connector alive."""

    def __init__(self, max_bytes: int = 256 << 20):
        self.max_bytes = max_bytes
        self._lock = threading.RLock()  # finalizer-reentrant, as above
        self._entries: OrderedDict = OrderedDict()
        self._bytes = 0
        self._watched: set[str] = set()
        #: ident -> content digest its entries were observed under
        self._content: dict[str, str] = {}

    @staticmethod
    def _size(batch: dict) -> int:
        total = 0
        for v in batch.values():
            if isinstance(v, tuple):
                for a in v:
                    total += getattr(a, "nbytes", 0) or 0
            else:
                total += getattr(v, "nbytes", 0) or 0
        return total

    def _sync_content_locked(self, ident: str, content: str) -> None:
        """Drop an ident's entries when its on-disk content changed."""
        if self._content.get(ident, content) != content:
            for k in [k for k in self._entries if k[0] == ident]:
                self._bytes -= self._size(self._entries.pop(k))
        self._content[ident] = content

    def get(self, connector, schema, table, start, count, columns):
        from trino_tpu import telemetry

        ident, content = _fingerprint(connector)
        k = (ident, schema, table, start, count, tuple(columns))
        with self._lock:
            self._sync_content_locked(ident, content)
            batch = self._entries.get(k)
            if batch is not None:
                self._entries.move_to_end(k)
                telemetry.SCAN_CACHE_HITS.inc(table=table)
                return batch
        telemetry.SCAN_CACHE_MISSES.inc(table=table)
        return None

    def put(self, connector, schema, table, start, count, columns, batch):
        size = self._size(batch)
        if size > self.max_bytes:
            return  # a batch bigger than the cache would evict everything
        ident, content = _fingerprint(connector)
        k = (ident, schema, table, start, count, tuple(columns))
        with self._lock:
            self._sync_content_locked(ident, content)
            if ident.startswith("id:") and ident not in self._watched:
                self._watched.add(ident)
                weakref.finalize(connector, self._drop_ident, ident)
            old = self._entries.pop(k, None)
            if old is not None:
                self._bytes -= self._size(old)
            self._entries[k] = batch
            self._bytes += size
            while self._bytes > self.max_bytes and self._entries:
                _, evicted = self._entries.popitem(last=False)
                self._bytes -= self._size(evicted)

    def _drop_ident(self, ident: str) -> None:
        with self._lock:
            self._watched.discard(ident)
            self._content.pop(ident, None)
            for k in [k for k in self._entries if k[0] == ident]:
                self._bytes -= self._size(self._entries.pop(k))

    def invalidate(self, connector, schema: str, table: str) -> None:
        ident, _content = _fingerprint(connector)
        with self._lock:
            dead = [
                k for k in self._entries
                if k[0] == ident and k[1:3] == (schema, table)
            ]
            for k in dead:
                self._bytes -= self._size(self._entries.pop(k))

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0
            self._content.clear()


#: the process-wide cache every LocalExecutor scans through
SHARED = ScanPageCache()

#: the process-wide streamed-split host-batch cache
SHARED_SPLITS = SplitBatchCache()
