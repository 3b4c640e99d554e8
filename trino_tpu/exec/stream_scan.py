"""Out-of-core split-granular streamed storage scans.

The storage half of the spill tier (exec/spill.py): where
``spill.scan_chunk_pages`` streams *generator-backed* connectors by
slicing arbitrary row ranges, real columnar storage wants the chunk
boundary to follow the file's own structure — row groups — so each
batch is one (coalesced) footer-pruned unit, read through the
connector's split path with partition/min-max pruning applied BEFORE
any data page is decoded (the ConnectorPageSource + ParquetReader
pairing, SPI/connector/ConnectorPageSource.java:24 over
lib/trino-parquet/.../reader/ParquetReader.java:85).

Shape discipline: every batch pads to ONE canonical capacity
(exec/shapes bucket of the budget-derived chunk rows), so the whole
stream — and any other query sharing the operator mix — executes one
compiled XLA program (PR 6's bucketing contract; streaming must not
re-open the compile tax).

Memory discipline: each batch's device working set is reserved through
the query's MemoryContext for the duration of the chain program, so
``query_max_memory_per_node`` governs the stream honestly and the
pool's high-water mark reflects real residency. The per-chunk /
final decomposition (partial aggregation, chunk-local TopN/Limit,
device-sorted runs host-merged) is spill's ``_split_chain`` — early
aggregation is what lets an SF100 scan-aggregate finish inside a
2 GiB budget.

Fault discipline: every split read passes a ``scan-read`` chaos gate
and retries AT SPLIT GRANULARITY — a mid-stream read failure re-reads
one split, never the table.

Read batches are cached in ``scan_cache.SHARED_SPLITS`` (byte-bounded
LRU) keyed by (connector, table, range, columns, domains), so hot
working sets stay warm without pinning an SF100 table in host memory.
"""

from __future__ import annotations

import numpy as np

from trino_tpu import fault, telemetry
from trino_tpu.connectors.base import ColumnDomain, Split
from trino_tpu.exec import scan_cache, shapes, spill
from trino_tpu.page import Column, Page, pad_capacity
from trino_tpu.plan import nodes as P

__all__ = ["eligible", "run_chain_streamed", "SCAN_READ_ATTEMPTS"]

#: split-read retry bound (transient I/O + injected scan-read faults)
SCAN_READ_ATTEMPTS = 3


def _connector(ex, node: P.TableScan):
    try:
        return ex.metadata.connector(node.catalog)
    except KeyError:
        return None


def budget_bytes(ex) -> int:
    """The governing budget: the explicit streaming budget when set,
    else the per-node memory cap (streaming is how a big scan FITS the
    cap, so the cap is the budget)."""
    return ex.hbm_budget() or ex._per_node_cap()


def eligible(ex, node: P.TableScan) -> bool:
    """Stream when the connector can iterate splits and the estimated
    scan would occupy more than a quarter of the budget resident."""
    from trino_tpu import session_properties as SP

    conn = _connector(ex, node)
    if conn is None or not getattr(conn, "streamable", False):
        return False
    if not SP.get(ex.session, "streaming_scan_enabled"):
        return False
    budget = budget_bytes(ex)
    if not budget:
        return False
    if node.split is not None:
        rows = int(node.split[1])
    else:
        try:
            rows = conn.row_count(node.schema, node.table)
        except Exception:
            return False
    return rows * spill.row_bytes(node.outputs) > budget // 4


def _resident_estimate(ex, node: P.TableScan, whole_table: bool):
    """Bytes a streamable scan that will NOT stream materializes —
    of its split, or of the ``whole_table`` — where a per-node cap
    governs it; None where nothing does (no cap, a connector that
    cannot stream, an unknown row count)."""
    conn = _connector(ex, node)
    if conn is None or not getattr(conn, "streamable", False):
        return None
    if not ex._per_node_cap():
        return None
    if node.split is not None and not whole_table:
        rows = int(node.split[1])
    else:
        try:
            rows = conn.row_count(node.schema, node.table)
        except Exception:
            return None
    return rows * spill.row_bytes(node.outputs)


def enforce_resident_fits(ex, node: P.TableScan) -> None:
    """A streamable scan that will NOT stream must fit the per-node cap
    resident: probe-reserve the materialized page bytes through the
    query's memory context so an over-budget table fails loudly with
    ``ExceededMemoryLimitError`` (naming the cap and the query) instead
    of silently blowing host/device memory. The probe frees
    immediately — the real pages reserve as they materialize."""
    est = _resident_estimate(ex, node, whole_table=False)
    if est is None or est <= ex._per_node_cap():
        return
    ctx = ex.memory_ctx.child("scan-resident")
    ctx.reserve(est)  # raises ExceededMemoryLimitError over the cap
    ctx.free(est)


def table_fits_resident(ex, node: P.TableScan) -> bool:
    """Whether the WHOLE table of a split scan may stay on the device:
    ``enforce_resident_fits``' judgement over the table's rows instead
    of the split's, and under an explicit HBM budget the quarter above
    which a scan of the table streams (``LocalExecutor._execute_impl``).
    A table over either keeps its split scans task-sized."""
    budget = ex.hbm_budget()
    if budget and spill.scan_bytes(ex.metadata, node) > budget // 4:
        return False
    est = _resident_estimate(ex, node, whole_table=True)
    return est is None or est <= ex._per_node_cap()


def _domains_of(node: P.TableScan) -> dict | None:
    if not node.domains:
        return None
    return {c: ColumnDomain(*dom) for c, dom in node.domains.items()}


def _domain_key(domains: dict | None) -> tuple:
    """Stable fingerprint of the pushed-down domains — part of the
    batch-cache key, since domains change the rows a read returns."""
    if not domains:
        return ()
    return tuple(
        (c, d.lo, d.hi, d.lo_strict, d.hi_strict)
        for c, d in sorted(domains.items())
    )


def _read_ranges(ex, conn, node: P.TableScan, chunk_rows: int):
    """Enumerate the read ranges: connector splits (partition +
    row-group pruned from the scan's domains), clipped to the bound
    split if this is one fleet task's share, sub-chunked to the
    budget-derived row bound. Returns (ranges, domains, prune metrics).
    """
    domains = _domains_of(node)
    n = conn.row_count(node.schema, node.table)
    lo, hi = 0, n
    if node.split is not None:
        lo = int(node.split[0])
        hi = min(n, lo + int(node.split[1]))
    target = max(1, -(-max(hi - lo, 1) // chunk_rows))
    splits = conn.splits(node.schema, node.table, target, domains=domains)
    metrics = dict(getattr(conn, "scan_metrics", None) or {})
    ranges: list[tuple[int, int]] = []
    for s in splits:
        a, b = max(s.start, lo), min(s.start + s.count, hi)
        while a < b:
            c = min(chunk_rows, b - a)
            ranges.append((a, c))
            a += c
    return ranges, domains, metrics


def _read_batch(ex, conn, node: P.TableScan, start: int, count: int,
                domains, dom_key: tuple):
    """One split-range read: LRU batch cache in front, scan-read chaos
    gate + split-granular retry behind."""
    cols = list(node.assignments.values())
    key_cols = tuple(cols) + dom_key
    cacheable = getattr(conn, "cacheable", False)
    if cacheable:
        batch = scan_cache.SHARED_SPLITS.get(
            conn, node.schema, node.table, start, count, key_cols
        )
        if batch is not None:
            return batch
    tag = f"{node.schema}.{node.table}:{start}"
    last: BaseException | None = None
    for attempt in range(SCAN_READ_ATTEMPTS):
        try:
            fault.check("scan-read", tag=tag, attempt=attempt)
            batch = conn.scan(
                node.schema, node.table, cols,
                Split(node.table, start, count), domains=domains,
            )
            break
        except (fault.InjectedFault, OSError) as e:
            last = e
    else:
        raise last  # type: ignore[misc]
    if cacheable:
        scan_cache.SHARED_SPLITS.put(
            conn, node.schema, node.table, start, count, key_cols, batch
        )
    return batch


def _batch_page(node: P.TableScan, batch: dict, count: int,
                capacity: int) -> Page:
    """Host batch -> device page at the canonical stream capacity."""
    import jax.numpy as jnp

    names = list(node.assignments)
    cols = []
    m = None
    for sym, cname in node.assignments.items():
        v = batch[cname]
        valid = None
        if isinstance(v, tuple):
            v, valid = v
        if m is None:
            m = len(v)
        cols.append(Column.from_numpy(
            node.outputs[sym], v, valid=valid, capacity=capacity
        ))
    if m is None:
        m = count
    mask = np.zeros(capacity, dtype=np.bool_)
    mask[:m] = True
    return Page(names, cols, jnp.asarray(mask), known_rows=m, packed=True)


def run_chain_streamed(
    ex, chain: list[P.PlanNode], scan: P.TableScan
) -> Page:
    """Execute chain-over-scan without materializing the table: iterate
    pruned split ranges, run the per-chunk part of the chain on each
    batch, spill outputs to host, then run the final part over the
    merged result (spill.run_chain_streamed with storage-aware
    chunking, pushdown, caching, retry, and honest accounting)."""
    from trino_tpu import session_properties as SP

    conn = _connector(ex, scan)
    budget = budget_bytes(ex)
    per_row = spill.row_bytes(scan.outputs)
    chunk_rows = spill.chunk_rows_for(budget, per_row)
    override = int(SP.get(ex.session, "max_chunk_rows") or 0)
    if override:
        chunk_rows = max(pad_capacity(min(chunk_rows, override)), 8)
    capacity = shapes.bucket(chunk_rows, site="stream-scan")
    per_chunk, final, merge_keys = spill._split_chain(chain)
    limit_needed = None
    if per_chunk and isinstance(per_chunk[-1], P.Limit):
        c = per_chunk[-1].count
        limit_needed = c if c >= 0 else None
    ranges, domains, metrics = _read_ranges(ex, conn, scan, chunk_rows)
    dom_key = _domain_key(domains)
    page_budget = 2 * capacity * per_row  # upload + chain working set
    ctx = ex.memory_ctx.child("stream-scan")
    runs: list[spill.HostRun] = []
    collected = 0
    batches = 0
    for start, count in ranges:
        ex._check_cancel()
        batch = _read_batch(ex, conn, scan, start, count, domains, dom_key)
        with ctx.reserving(page_budget):
            page = _batch_page(scan, batch, count, capacity)
            out = (
                ex._run_chain(list(per_chunk), page) if per_chunk else page
            )
            out = ex._compact(out)
            run = spill.page_to_host(out)
        batches += 1
        telemetry.SCAN_BATCHES.inc(table=scan.table)
        if run.n_rows:
            runs.append(run)
            collected += run.n_rows
        if limit_needed is not None and collected >= limit_needed:
            break
    if not runs:
        runs = [spill._empty_run((per_chunk or [scan])[-1].outputs)]
    if merge_keys is not None and len(runs) > 1:
        runs = [spill.merge_sorted_runs(runs, merge_keys)]
    ex.scan_log.append({
        "table": f"{scan.schema}.{scan.table}",
        "streamed": True,
        "batches": batches,
        "rowgroups_total": int(metrics.get("rowgroups_total", 0)),
        "rowgroups_pruned": int(metrics.get("rowgroups_pruned", 0)),
        "partitions_pruned": int(metrics.get("partitions_pruned", 0)),
        "splits": int(metrics.get("splits", 0)),
    })
    combined = spill.host_concat_to_page(ex, runs)
    if final:
        return ex._run_chain(list(final), combined)
    return combined
