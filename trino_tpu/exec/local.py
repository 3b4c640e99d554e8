"""Single-fragment plan execution over device pages.

The analog of the reference's LocalExecutionPlanner + Driver
(MAIN/sql/planner/LocalExecutionPlanner.java:527,
MAIN/operator/Driver.java:66) collapsed into a batch-synchronous tree
walk: each plan node consumes whole device Pages and produces one —
there is no page-at-a-time pull loop because a TPU wants one large
batched computation per operator, not 4KB batches. Host syncs happen
only at capacity decisions (join fan-out, group counts), mirroring the
reference's build-side barriers.
"""

from __future__ import annotations

import os
import time
from dataclasses import replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import fault, memory, program_catalog, telemetry
from trino_tpu import types as T
from trino_tpu.exec import kernels as K
from trino_tpu.exec import scan_cache, shapes, stage
from trino_tpu.exec.aggregates import compute_aggregate
from trino_tpu.expr.compiler import ColumnLayout, compile_expr
from trino_tpu.expr.ir import AggCall, Call, Cast, InputRef, RowExpression
from trino_tpu.metadata import Metadata, Session
from trino_tpu.page import Column, Page, pad_capacity, unify_dictionaries
from trino_tpu.plan import nodes as P

__all__ = ["LocalExecutor", "QueryCancelled"]


def _hash_varchar_column(t, values, valid, capacity) -> Column:
    """Build a hash-coded varchar column: [hash64, source_row_id]
    lanes + a host string pool (one hash pass + a one-time injectivity
    proof — no sorted-dictionary build). On the astronomically rare
    hash collision, fall back to dictionary coding."""
    from trino_tpu.page import HashCollision, HashStringPool

    pool = HashStringPool(values)
    try:
        pool.verify_injective()
    except HashCollision:
        return Column.from_numpy(t, values, valid=valid, capacity=capacity)
    n = len(values)
    data = np.zeros((capacity, 2), dtype=np.int64)
    data[:n, 0] = pool.hashes()
    data[:n, 1] = np.arange(n)
    col_valid = None
    if valid is not None:
        v = np.zeros(capacity, dtype=np.bool_)
        v[:n] = valid
        col_valid = jnp.asarray(v)
    return Column(t, jnp.asarray(data), col_valid, None, pool)


def _scan_column(t, raw, capacity, hashed: bool = False) -> Column:
    """One scanned connector value -> device Column (shared by the
    cached full-table scan and fleet split scans). ``raw`` is either a
    host array or a (values, valid) tuple."""
    valid = None
    if isinstance(raw, tuple):
        raw, valid = raw
    if hashed:
        return _hash_varchar_column(
            t, np.asarray(raw, dtype=object), valid, capacity
        )
    return Column.from_numpy(t, raw, valid=valid, capacity=capacity)


#: seconds a fired ``compile-delay`` fault stalls one dispatch
COMPILE_DELAY_ENV = "TRINO_TPU_COMPILE_DELAY_S"
DEFAULT_COMPILE_DELAY_S = 0.25


def _maybe_compile_delay() -> None:
    """``compile-delay`` fault hook on the chain-dispatch path.

    Unlike every other site, a fired fault here fails NOTHING: it
    sleeps inside a compile-kind child of the thread's trace anchor,
    so the stall lands in the flight recorder's xla_compile bucket —
    a deterministic stand-in for an XLA recompile storm that the
    performance sentry must detect and attribute on a WARMED statement
    (whose real programs are cached and never recompile)."""
    try:
        fault.check("compile-delay")
        return
    except fault.InjectedFault:
        pass
    delay = float(
        os.environ.get(COMPILE_DELAY_ENV, "") or DEFAULT_COMPILE_DELAY_S
    )
    parent = telemetry.active_span()
    sp = (
        parent.child("injected-compile-delay", "compile")
        if parent is not None else None
    )
    try:
        time.sleep(delay)
    finally:
        if sp is not None:
            sp.finish()


#: the plan operator a program that is one operator serves, by the
#: program's name (a mesh twin's, ``mesh_<name>``, as the local one's):
#: the ``op:<NodeType>`` scope every instruction of it is traced under
#: (``kernels.py`` has the grammar). A chain opens an ``op<i>:`` scope a
#: position itself (``stage.build_chain``) and is not listed.
PROGRAM_OPERATOR = {
    "scan_split": "TableScan",
    "compact": "Compact",
    "join_count": "Join",
    "join_bounds": "Join",
    "join_expand": "Join",
    "concat": "Join",  # the two halves of a skew-split join
    "semi_join": "SemiJoin",
    "cross_count": "CrossJoin",
    "cross_join": "CrossJoin",
    "dynamic_filter": "DynamicFilter",
    "unnest": "Unnest",
    "window": "Window",
    "group_id": "GroupId",
    "exchange": "Exchange",
    "exchange_in_place": "Exchange",
    "exchange_dest": "Exchange",
    "range_bits": "Exchange",
    "range_dest": "Exchange",
    "dest_hist": "Exchange",
}


def _named_jit(fn, name: str, scope: str | None = None):
    """``jax.jit`` of a program named by what it does: XLA's module, and
    with it the ``XLA Modules`` line of a device trace, reads
    ``jit_<name>`` (the fingerprint XLA appends tells two instances of
    one name apart). A name holds node types and phases, never a
    literal, a capacity or a hash. A program that is not a chain is
    traced under its operator's scope (``PROGRAM_OPERATOR``; a name
    that is not listed there is an error, so no program is left without
    one); a chain under ``scope`` where it has work outside its
    positions' own scopes (the mesh's way into the shards)."""
    local_name = name.removeprefix("mesh_")
    if not local_name.startswith("chain_"):
        scope = "op:" + PROGRAM_OPERATOR[local_name]
    if scope is not None:
        body = fn

        def fn(*args):
            with jax.named_scope(scope):
                return body(*args)

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _chain_program_name(chain) -> str:
    """``chain_TableScan_Filter_Aggregate``-style name from the node
    list the catalog label is made of, capped in length."""
    return ("chain_" + "_".join(type(n).__name__ for n in chain))[:96]


class _dispatching:
    """``with _dispatching(program, miss):`` around one program call: a
    ``dispatch`` span and, on a jit-cache miss, a ``build_trace`` child
    over what the miss costs the host (the program's Python build,
    ``jax.jit``, and the first call: trace, lowering, a compile or its
    cache read). ``note`` adds attributes known only after the call."""

    __slots__ = ("_outer", "_inner")

    def __init__(self, program: str, miss: bool):
        self._outer = telemetry.child_span(
            "dispatch", program=program, miss=miss
        )
        self._inner = telemetry.child_span("build_trace") if miss else None

    def __enter__(self):
        self._outer.__enter__()
        if self._inner is not None:
            self._inner.__enter__()
        return self

    def note(self, **attrs):
        if self._outer.span is not None:
            self._outer.span.attrs.update(attrs)

    def note_join(self, n_build: int, key_bits: int = 64,
                  kind: str = "inner"):
        """The program holds a ``kernels.join_ranges`` over a build
        side of ``n_build`` rows, its keys ranked at ``key_bits`` bits:
        note which search that was built with (``join_search``:
        ``count`` / ``sort``; ``build_rows``), the width
        (``key_bits``: 64, or what the plan's exact key range needs)
        and what it joins as (``join_kind``: the ``Join``'s kind,
        ``semi``, or ``anti`` — a semi join whose match the plan
        negates), and count it."""
        search = K.join_search(n_build)
        self.note(join_search=search, build_rows=n_build, key_bits=key_bits,
                  join_kind=kind)
        telemetry.JOINS.inc(search=search, key_bits=str(key_bits))
        if key_bits >= 64:
            telemetry.WIDE_KEY_JOINS.inc()
        field = telemetry.JOIN_KIND_FIELDS.get(kind)
        if field is not None:
            telemetry.JOIN_KIND_COUNTERS[field].inc()

    def note_distinct(self, chain):
        """Count the DISTINCT aggregate calls of a chain program."""
        n = sum(
            call.distinct
            for nd in chain if isinstance(nd, P.Aggregate)
            for call in nd.aggregates.values()
        )
        if n:
            self.note(distinct_aggregates=n)
            telemetry.DISTINCT_AGGREGATES.inc(n)

    def __exit__(self, *exc):
        if self._inner is not None:
            self._inner.__exit__(*exc)
        self._outer.__exit__(*exc)


class QueryCancelled(RuntimeError):
    """Raised inside the executor when the query's cancel event fires
    (cooperative cancellation: in-flight device dispatches finish, the
    next operator boundary aborts — the reference cancels at its
    driver-quantum boundaries the same way, MAIN/operator/Driver.java)."""


class LocalExecutor:
    """Executes a logical plan tree on the local devices.

    Per-node device computations are traced into jitted programs cached
    by (plan structure, input layout, capacity) — the analog of the
    reference's per-query bytecode generation with
    PageFunctionCompiler's cache (MAIN/sql/gen/PageFunctionCompiler.java:102).
    Scanned tables are cached device-resident (a worker's memory
    connector analog), so repeated queries pay no host->HBM transfer.
    """

    def __init__(self, metadata: Metadata, session: Session):
        self.metadata = metadata
        self.session = session
        # feed trino_xla_compile_total/_seconds_total from jax's own
        # compile events (idempotent process-wide hook)
        telemetry.install_jax_compile_hook()
        #: structural key -> (jitted fn, host metadata); hit/miss rates
        #: surface as trino_jit_cache_{hits,misses}_total{cache="local"}
        self._jit_cache: dict = telemetry.CountingCache("local")
        #: dynamic-filter effectiveness log (tests + EXPLAIN ANALYZE):
        #: [{rows_in, rows_kept, pairs}] per join probe this executor ran
        self.df_log: list[dict] = []
        #: storage-scan pruning/streaming log (tests + EXPLAIN ANALYZE):
        #: [{table, rowgroups_total, rowgroups_pruned, partitions_pruned,
        #:   batches?, streamed?}] per pruned or streamed scan
        self.scan_log: list[dict] = []
        #: worker-local memory pool: every device allocation path
        #: reserves through a MemoryContext rooted here, and the
        #: per-node cap (query_max_memory_per_node) is enforced at
        #: reservation time
        self.memory_pool = memory.MemoryPool(
            limit_provider=self._per_node_cap
        )
        #: active query context — swapped per query by QueryRunner /
        #: the worker task loop; a default exists so direct executor
        #: use (tests, EXPLAIN) never needs getattr guards
        self.memory_ctx = self.memory_pool.query_context("adhoc")
        #: joins revoked into the spill tier by memory pressure
        #: (count of MemoryRevokingScheme-analog conversions)
        self.memory_revocations = 0
        #: match symbol of the SemiJoin about to run under a Filter
        #: that negates it (``_note_negated_match``)
        self._negated_match: str | None = None
        #: revocation budget in force while a revoked subtree runs
        #: (makes hbm_budget() nonzero so spill paths chunk under it)
        self._revoked_budget = 0
        #: grace-join observability counters (exec.spill writes these;
        #: real fields so call sites never need getattr defaults)
        self.grace_recursion_hwm = 0
        self.grace_hot_pairs = 0
        #: cooperative cancellation: set by the coordinator, checked at
        #: operator boundaries
        self.cancel_event = None
        #: absolute monotonic deadline (query_max_execution_time): set
        #: by the engine per statement, checked at the same boundaries
        self.deadline = None
        #: batched chain prefetch results: id(chain top node) ->
        #: (node, Page) — populated by _prefetch_join_chains, consumed
        #: by execute(); holding the node object pins its id
        self._prefetched: dict = {}
        #: the Output node's source: the FINAL chain defers its
        #: flags/count sync into the result transfer (one fewer host
        #: round trip per query)
        self._defer_sync_for: P.PlanNode | None = None
        #: per-operator profiler (trino_tpu.profiler.OperatorProfiler)
        #: set for the duration of one query/task; None = no profiling
        self.profiler = None
        #: jit-cache key -> abstract (env, mask) avals captured at
        #: dispatch time, feeding lazy XLA cost analysis
        self._chain_avals: dict = {}
        #: jit-cache key -> {"flops", "bytes_accessed"} | None (the
        #: lazy cost cache; None records an analysis that failed so it
        #: is never retried)
        self._chain_costs: dict = {}
        #: per-query cache.CacheStats sink (set by the engine around
        #: each statement; None = device-tier traffic not attributed)
        self.cache_stats = None

    def hbm_budget(self) -> int:
        """Device-memory budget in bytes (session ``hbm_budget_bytes``;
        0 = resident mode). Tables/joins whose working sets exceed it
        stream through exec.spill instead of materializing. While a
        memory-revoked subtree runs, the per-node cap stands in as the
        budget so the whole subtree degrades into the spill tier."""
        from trino_tpu import session_properties as SP

        budget = int(SP.get(self.session, "hbm_budget_bytes"))
        return budget or self._revoked_budget

    def _per_node_cap(self) -> int:
        """query_max_memory_per_node in bytes (0 = unlimited)."""
        from trino_tpu import session_properties as SP

        return SP.parse_data_size(
            SP.get(self.session, "query_max_memory_per_node")
        )

    @property
    def tracked_bytes_hwm(self) -> int:
        """Largest tracked device working set this executor ever
        reserved (lifetime pool high-water mark; the budget-tier tests
        assert it stays within hbm_budget_bytes). Pre-governance this
        was an ad-hoc field; it is now derived from the memory pool."""
        return self.memory_pool.peak_bytes

    def invalidate_scan(self, catalog: str, schema: str, table: str):
        """Drop cached device pages for a table (called after writes —
        the reference's memory connector versions table handles the
        same way). Pages live in the process-wide shared cache, so a
        write through this executor also invalidates every concurrent
        reader of the same connector. Learned statistics (filter
        selectivities, group-by capacities) are dropped with it: they
        were observed against the pre-write data and would otherwise
        persist stale forever."""
        try:
            connector = self.metadata.connector(catalog)
        except KeyError:
            connector = None
        if connector is not None:
            scan_cache.SHARED.invalidate(connector, schema, table)
            scan_cache.SHARED_SPLITS.invalidate(connector, schema, table)
            if hasattr(connector, "invalidate"):
                connector.invalidate(schema, table)
            # cross-query cache tiers: bump the generation counter so
            # device pages and semantic results built over the old data
            # revalidate stale on their next probe, and drop this
            # process's pinned device entries eagerly
            from trino_tpu import cache as xcache

            ident, _content = xcache.connector_fingerprint(connector)
            xcache.GENERATIONS.bump(ident, schema, table)
            xcache.DEVICE.invalidate(ident, schema, table)
        for k in [
            k for k in self._jit_cache
            if isinstance(k, tuple) and k and k[0] in ("selectivity", "caps")
        ]:
            del self._jit_cache[k]

    def _device_cache_on(self) -> bool:
        """Session gate for the cross-query HBM tier (cache.DEVICE)."""
        from trino_tpu import session_properties as SP

        try:
            return bool(SP.get(self.session, "device_cache_enabled"))
        except Exception:
            return False

    def _cache_tokens(self, connector, schema: str, table: str) -> tuple:
        """Single-table staleness validators for a device-cache entry."""
        from trino_tpu import cache as xcache

        ident, _content = xcache.connector_fingerprint(connector)
        try:
            version = connector.table_version(schema, table)
        except Exception:
            version = 0
        return ((
            ident, schema, table,
            xcache.GENERATIONS.get(ident, schema, table), version,
        ),)

    def _check_cancel(self):
        if self.cancel_event is not None and self.cancel_event.is_set():
            raise QueryCancelled("Query was canceled")
        if self.deadline is not None and time.monotonic() > self.deadline:
            from trino_tpu.tracker import QueryDeadlineExceededError

            raise QueryDeadlineExceededError(
                "Query exceeded maximum execution time limit "
                "[query_max_execution_time]"
            )

    def execute(self, node: P.PlanNode) -> Page:
        prof = self.profiler
        if prof is None:
            return self._execute_impl(node)
        rec = prof.open(self._op_label(node), type(node).__name__, id(node))
        try:
            out = self._execute_impl(node)
        except BaseException:
            prof.close(rec, None)
            raise
        prof.close(rec, out)
        return out

    @staticmethod
    def _op_label(node: P.PlanNode) -> str:
        """Display label for one profiled operator. A fused chain
        executes as one XLA program, so its head labels the whole
        chain; everything else is its node type."""
        if isinstance(node, stage.FUSABLE):
            names = []
            cur = node
            while isinstance(cur, stage.FUSABLE):
                names.append(type(cur).__name__)
                cur = cur.sources[0]
            if len(names) > 1:
                return "→".join(reversed(names))
        return type(node).__name__

    def _execute_impl(self, node: P.PlanNode) -> Page:
        self._check_cancel()
        if isinstance(node, P.Output):
            # top of a query: drop any prefetch leftovers of a prior
            # (failed) query so node ids never alias across plans
            self._prefetched.clear()
        hit = self._prefetched.pop(id(node), None)
        if hit is not None and hit[0] is node:
            return hit[1]
        if isinstance(node, stage.FUSABLE):
            chain: list[P.PlanNode] = []
            cur = node
            while isinstance(cur, stage.FUSABLE):
                chain.append(cur)
                cur = cur.sources[0]
            if isinstance(cur, P.TableScan):
                from trino_tpu.exec import stream_scan

                if stream_scan.eligible(self, cur):
                    return stream_scan.run_chain_streamed(
                        self, list(reversed(chain)), cur
                    )
                budget = self.hbm_budget()
                if budget:
                    from trino_tpu.exec import spill

                    if spill.scan_bytes(self.metadata, cur) > budget // 4:
                        return spill.run_chain_streamed(
                            self, list(reversed(chain)), cur
                        )
                # not streaming (disabled or ineligible): the resident
                # materialization must still fit the per-node cap
                stream_scan.enforce_resident_fits(self, cur)
            self._note_negated_match(chain[-1], cur)
            base = self.execute(cur)
            return self._run_chain(list(reversed(chain)), base)
        m = getattr(self, f"_{type(node).__name__}", None)
        if m is None:
            raise NotImplementedError(f"no executor for {type(node).__name__}")
        return m(node)

    def _note_negated_match(self, above: P.PlanNode, node: P.PlanNode):
        """``node`` is about to run under ``above``: where it is a
        SemiJoin whose match ``above`` filters on negated, remember the
        symbol for ``_take_negated_match``."""
        anti = isinstance(node, P.SemiJoin) and node.negated_by(above)
        self._negated_match = node.match_symbol if anti else None

    def _take_negated_match(self, node: P.SemiJoin) -> str:
        """``anti`` where the Filter over ``node`` negates its match,
        else ``semi``. Asked before the sources run: a SemiJoin below
        would take the note for its own."""
        anti = self._negated_match == node.match_symbol
        self._negated_match = None
        return "anti" if anti else "semi"

    # ---- fused pipelines -------------------------------------------------

    @staticmethod
    def _node_key(n: P.PlanNode):
        if isinstance(n, P.Filter):
            return ("F", repr(n.predicate))
        if isinstance(n, P.Project):
            return ("P", tuple((s, repr(e)) for s, e in n.assignments.items()))
        if isinstance(n, P.Aggregate):
            return (
                "A", tuple(n.group_keys),
                tuple(
                    (s, a.name, a.distinct, repr(a.args), repr(a.filter))
                    for s, a in n.aggregates.items()
                ),
                n.step,
                # compiled programs bake key shift offsets/widths, so
                # different ranges must compile separately
                None if n.key_ranges is None else tuple(
                    sorted(n.key_ranges.items())
                ),
            )
        if isinstance(n, (P.Sort, P.TopN)):
            return (
                "S",
                tuple((k.symbol, k.ascending, k.nulls_first) for k in n.keys),
                getattr(n, "count", None),
            )
        if isinstance(n, P.Limit):
            return ("L", n.count, n.offset)
        raise NotImplementedError(type(n).__name__)

    def _run_chain(self, chain: list[P.PlanNode], page: Page) -> Page:
        self._check_cancel()  # also covers streamed per-chunk calls
        return self._run_chain_inner(chain, page)

    def _run_chain_inner(self, chain: list[P.PlanNode], page: Page) -> Page:
        """Run a fused operator chain: one jitted program, one dispatch.

        Grouped aggregations retry with 8x larger slot tables when the
        returned overflow flag trips; the learned capacity persists per
        chain shape so repeated queries never re-overflow (capacity
        hysteresis — the FlatHash table survives across pages in the
        reference, MAIN/operator/FlatHash.java:316).

        With session property ``max_chunk_rows`` set, aggregations over
        wider inputs run memory-bounded: partial aggregation per row
        chunk, then a FINAL combine over the concatenated partials —
        the working-set analog of the reference's spillable aggregation
        (MAIN/operator/aggregation/builder/SpillableHashAggregationBuilder.java:46);
        the chunk partials play the role of spilled sorted runs."""
        agg_arr = next(
            (
                i for i, n in enumerate(chain)
                if isinstance(n, P.Aggregate)
                and any(
                    c.name in ("array_agg", "map_agg")
                    for c in n.aggregates.values()
                )
            ),
            None,
        )
        if agg_arr is not None:
            # array construction is host-resident by design (pools);
            # the aggregation runs as a host group-by
            return self._host_array_agg(chain, agg_arr, page)
        # adaptive filter split: a selective leading Filter shrinks the
        # working capacity for the whole rest of the chain (dead-row
        # sorts/gathers dominate otherwise). Selectivity is learned per
        # chain shape; non-selective filters stay fused (the split
        # costs one extra sync + compaction). At first sight the
        # planner's column-stats estimate decides: a filter it expects
        # to keep most rows runs fused at once, so the statement's
        # SECOND run dispatches the programs of its first (learning
        # "not selective" only after a split run compiled the fused
        # program on the second run — Q1 at SF1).
        if (
            len(chain) > 1
            and isinstance(chain[0], P.Filter)
            and page.capacity >= (1 << 18)
            and any(
                isinstance(n, (P.Aggregate, P.Sort, P.TopN))
                for n in chain[1:]
            )
        ):
            skey = (
                "selectivity", self._node_key(chain[0]), page.capacity,
            )
            sel = self._jit_cache.get(skey)
            if sel is None:
                sel = self._estimated_selectivity(chain[0])
            if sel is None or sel <= 0.5:
                filtered = self._run_chain(chain[:1], page)
                self._jit_cache[skey] = (
                    filtered.num_rows() / page.capacity
                )
                return self._run_chain(chain[1:], filtered)

        from trino_tpu import session_properties as SP

        chunk_rows = int(SP.get(self.session, "max_chunk_rows"))
        if chunk_rows > 0 and page.capacity > chunk_rows:
            # only SINGLE-step aggregations chunk: the FINAL combine
            # over the partial states materializes O(distinct keys),
            # like the reference's spill merge pass
            agg_i = next(
                (
                    i for i, n in enumerate(chain)
                    if isinstance(n, P.Aggregate)
                    and n.group_keys
                    and n.step == "SINGLE"
                ),
                None,
            )
            if (
                agg_i is not None
                and not any(
                    c.distinct
                    for c in chain[agg_i].aggregates.values()
                )
                # every pre node must be row-local: a Limit/Sort/TopN
                # before the aggregate is global, not per-chunk
                and all(
                    isinstance(n, (P.Filter, P.Project))
                    for n in chain[:agg_i]
                )
                and _splittable(chain[agg_i])
            ):
                return self._run_chain_chunked(
                    chain, page, agg_i, chunk_rows
                )
        caps_key = (
            "caps", tuple(self._node_key(n) for n in chain), page.capacity
        )
        learned = self._jit_cache.get(caps_key)
        if learned is not None:
            caps = {i: list(v) for i, v in learned.items()}
        else:
            caps = stage.plan_capacities(chain, page.capacity)
        while True:
            if page.ordered_on is not None and self._jit_cache.get(
                _unordered_key(caps_key)
            ):
                # this chain's input broke its declared order (in an
                # earlier statement, or in the run just made): by sort
                page = dc_replace(page, ordered_on=None)
            env, mask, flags, n_live_dev, out_layout = self._dispatch_chain(
                chain, page, caps
            )
            if (
                chain and chain[-1] is self._defer_sync_for
                and getattr(self, "_defer_ok", False)
            ):
                # final chain of the query: skip the sync — the result
                # transfer fetches the overflow flags alongside the
                # data, and the engine retries the query if one
                # tripped (learned capacities make that the rare path)
                out = Page(
                    list(out_layout.names),
                    [
                        Column(
                            out_layout.types[s], env[s][0], env[s][1],
                            out_layout.dicts.get(s),
                            out_layout.pools.get(s),
                            out_layout.arrays.get(s),
                        )
                        for s in out_layout.names
                    ],
                    mask,
                )
                out.pending_flags = (flags, caps_key, caps)
                return out
            # one host sync fetches overflow flags AND the live count,
            # so downstream consumers (compact, joins, result fetch)
            # never re-sync
            with telemetry.child_span("host_sync", site="chain_flags"):
                vals, n_live = jax.device_get((flags, n_live_dev))
            if vals and self.note_chain_flags(vals, caps_key, caps):
                continue
            return self._finalize_chain(
                chain, env, mask, int(n_live), out_layout
            )

    def _host_array_agg(self, chain, agg_i: int, page: Page) -> Page:
        """array_agg as a host group-by building ArrayPools (the
        reference's ArrayAggregationFunction materializes per-group
        BlockBuilders the same way; pools are host-side here by
        design). NULL inputs are skipped. Other aggregates cannot mix
        with array_agg in one GROUP BY yet."""
        from trino_tpu.exec.spool import page_to_host

        nd = chain[agg_i]
        for call in nd.aggregates.values():
            if call.name not in ("array_agg", "map_agg"):
                raise NotImplementedError(
                    "array_agg/map_agg cannot combine with other "
                    "aggregates in one GROUP BY yet"
                )
            if len(call.args) != (2 if call.name == "map_agg" else 1):
                raise NotImplementedError(
                    f"{call.name} argument count"
                )
        pre = list(chain[:agg_i])
        # computed arguments materialize through an inserted Project so
        # the host group-by below reads plain columns
        if any(
            not isinstance(a, InputRef)
            for call in nd.aggregates.values() for a in call.args
        ):
            src_outputs = (
                pre[-1].outputs if pre
                else {
                    nm: c.type for nm, c in zip(page.names, page.columns)
                }
            )
            assigns = {
                s: InputRef(t, s) for s, t in src_outputs.items()
            }
            new_aggs = {}
            for sym, call in nd.aggregates.items():
                new_args = []
                for j, a in enumerate(call.args):
                    if isinstance(a, InputRef):
                        new_args.append(a)
                    else:
                        tmp = f"{sym}__arg{j}"
                        assigns[tmp] = a
                        new_args.append(InputRef(a.type, tmp))
                new_aggs[sym] = dc_replace(call, args=tuple(new_args))
            proj = P.Project(
                {s: e.type for s, e in assigns.items()},
                source=nd.sources[0] if nd.sources else None,
                assignments=assigns,
            )
            pre.append(proj)
            nd = dc_replace(nd, aggregates=new_aggs)
        if pre:
            page = self._run_chain(pre, page)
        payload = page_to_host(self._compact(page))
        col_of = dict(zip(payload["names"], payload["cols"]))
        type_of = dict(zip(payload["names"], payload["types"]))
        n = len(payload["cols"][0][0]) if payload["cols"] else 0
        keys = list(nd.group_keys)
        if keys:
            lanes = []
            for k in reversed(keys):
                v, valid = col_of[k]
                if v.dtype == object or v.dtype.kind == "U":
                    _u, codes = np.unique(v.astype(str), return_inverse=True)
                    lanes.append(codes)
                else:
                    lanes.append(v)
                if valid is not None:
                    lanes.append((~valid).astype(np.int8))
            order = np.lexsort(lanes)
        else:
            order = np.arange(n)
        # group boundaries over the sorted rows
        def key_tuple(i):
            out = []
            for k in keys:
                v, valid = col_of[k]
                out.append(
                    None if (valid is not None and not valid[i]) else v[i]
                )
            return tuple(out)

        groups: list[list[int]] = []
        last = object()
        for i in order:
            kt = key_tuple(i) if keys else ()
            if kt != last:
                groups.append([])
                last = kt
            groups[-1].append(i)
        if not keys and not groups:
            groups = [[]]
        out_named: dict[str, tuple] = {}
        for k in keys:
            v, valid = col_of[k]
            firsts = [g[0] for g in groups]
            kv = v[firsts] if len(firsts) else v[:0]
            kval = None if valid is None else valid[firsts]
            out_named[k] = (type_of[k], kv, kval)
        for sym, call in nd.aggregates.items():
            if call.name == "map_agg":
                # one (key, value) entry per row with a non-NULL key
                # (MapAggAggregationFunction semantics)
                kv, kvalid = col_of[call.args[0].name]
                vv, vvalid = col_of[call.args[1].name]
                maps = np.empty(len(groups), dtype=object)
                for gi, g in enumerate(groups):
                    maps[gi] = [
                        (
                            kv[i],
                            None if (vvalid is not None and not vvalid[i])
                            else vv[i],
                        )
                        for i in g
                        if kvalid is None or kvalid[i]
                    ]
                out_named[sym] = (nd.outputs[sym], maps, None)
                continue
            src = call.args[0].name
            v, valid = col_of[src]
            lists = np.empty(len(groups), dtype=object)
            for gi, g in enumerate(groups):
                lists[gi] = [
                    v[i] for i in g
                    if valid is None or valid[i]
                ]
            out_named[sym] = (nd.outputs[sym], lists, None)
        cap = shapes.bucket(max(len(groups), 1), site="agg-host")
        names, cols = [], []
        for s, (t, vals, valid) in out_named.items():
            names.append(s)
            cols.append(Column.from_numpy(t, vals, valid=valid, capacity=cap))
        m = np.zeros(cap, dtype=np.bool_)
        m[: len(groups)] = True
        out = Page(
            names, cols, jnp.asarray(m),
            known_rows=len(groups), packed=True,
        )
        if chain[agg_i + 1:]:
            return self._run_chain(chain[agg_i + 1:], out)
        return out

    def _canon_view(self, chain, page: Page):
        """(chain', page', out_map) with the chain rewritten to
        nameless normal form and the page pruned/renamed to match —
        the cache-key normalization that lets distinct queries sharing
        an operator mix resolve to one compiled program. out_map is
        None when bucketing is OFF or the chain has a construct the
        rewriter does not cover (callers then key on original names)."""
        if not shapes.enabled(self.session):
            return chain, page, None
        canon = shapes.canonicalize_chain(chain, list(page.names))
        if canon is None:
            return chain, page, None
        cols = dict(zip(page.names, page.columns))
        view = Page(
            list(canon.in_map.values()),
            [cols[o] for o in canon.in_map],
            page.mask,
            known_rows=page.known_rows, packed=page.packed,
            ordered_on=canon.in_map.get(page.ordered_on),
        )
        return canon.chain, view, canon.out_map

    def _dispatch_chain(self, chain, page: Page, caps):
        """Compile (cached) + dispatch one fused chain program without
        waiting for the result — callers sync when they need the flags
        and live count (batched across independent chains where
        possible)."""
        chain, page, out_map = self._canon_view(chain, page)
        key = (
            "chain",
            tuple(self._node_key(n) for n in chain),
            tuple((i, c[0]) for i, c in sorted(caps.items())),
            self._layout_sig(page),
            # an Aggregate over this key groups by runs: another program
            page.ordered_on,
        )
        hit = self._jit_cache.get(key)
        was_miss = hit is None
        program = _chain_program_name(chain)
        with _dispatching(program, was_miss) as dispatch:
            if was_miss:
                in_layout = stage.ChainLayout(
                    names=list(page.names),
                    types={
                        n: c.type for n, c in zip(page.names, page.columns)
                    },
                    dicts={
                        n: c.dictionary
                        for n, c in zip(page.names, page.columns)
                    },
                    capacity=page.capacity,
                    pools={
                        n: c.hash_pool
                        for n, c in zip(page.names, page.columns)
                        if c.hash_pool is not None
                    },
                    arrays={
                        n: c.array_pool
                        for n, c in zip(page.names, page.columns)
                        if c.array_pool is not None
                    },
                    ordered_on=page.ordered_on,
                )
                fn, out_layout = stage.build_chain(chain, in_layout, caps)

                tail = stage.op_scope(len(chain) - 1, chain[-1])

                def counted(env, mask, _fn=fn):
                    env2, mask2, flags = _fn(env, mask)
                    # the live count is the last operator's
                    with jax.named_scope(tail):
                        return env2, mask2, flags, K.count_true(mask2)

                hit = (_named_jit(counted, program), out_layout)
                self._jit_cache[key] = hit
            fn, out_layout = hit
            env_in = self._env(page)
            if key not in self._chain_avals:
                # shape metadata only — feeds lazy cost analysis without
                # touching device data or the dispatch hot path
                abstract = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    (env_in, page.mask),
                )
                self._chain_avals[key] = abstract
            if was_miss:
                # catalog the freshly built program; the resolver lowers
                # at the recorded avals on first cost/memory/HLO read
                program_catalog.CATALOG.register(
                    key, source="local",
                    label="→".join(type(n).__name__ for n in chain),
                    resolver=program_catalog.aot_resolver(
                        fn, self._chain_avals[key]
                    ),
                )
            else:
                program_catalog.CATALOG.note_hit(key)
            if self.profiler is not None:
                self.profiler.note_dispatch(key)
            _maybe_compile_delay()
            if was_miss:
                # the first call pays jit trace + backend compile (or a
                # persistent-cache deserialize) before the async dispatch
                # returns — that wall IS the program's compile cost
                t0 = time.perf_counter()
                env, mask, flags, n_live_dev = fn(env_in, page.mask)
                program_catalog.CATALOG.note_compile_seconds(
                    key, time.perf_counter() - t0
                )
            else:
                env, mask, flags, n_live_dev = fn(env_in, page.mask)
            dispatch.note_distinct(chain)
            if out_layout.groupbys:
                # each grouped Aggregate's path, in chain order
                # (telemetry.span_totals counts them onto the row)
                dispatch.note(
                    groupbys=[
                        path
                        for _pos, path in sorted(out_layout.groupbys.items())
                    ],
                    start_walks=sum(out_layout.start_walks.values()),
                )
        if out_map is not None:
            # the cached program speaks canonical names; translate its
            # outputs back for this call (the cached out_layout is
            # shared — never mutate it)
            out_layout, env = _rename_out(out_layout, env, out_map)
        return env, mask, flags, n_live_dev, out_layout

    def chain_cost(self, key) -> dict | None:
        """XLA cost model ({'flops', 'bytes_accessed'}) for one cached
        chain program, read through the process-wide program catalog —
        one lazy ``lower().compile()`` per program (a persistent-cache
        deserialize of what the dispatch path already built), shared
        with every other catalog consumer instead of recomputed per
        lookup. A failed analysis memoizes as None per executor so it
        is not retried per query."""
        if key in self._chain_costs:
            return self._chain_costs[key]
        if program_catalog.CATALOG.entry_for(key) is None:
            # executor restored from a snapshot / catalog evicted: the
            # program still lives in the jit cache, so re-catalog it.
            # plain dict.get: a cost lookup is not a cache hit/miss
            # event (CountingCache feeds trino_jit_cache_* counters)
            hit = dict.get(self._jit_cache, key)
            abstract = self._chain_avals.get(key)
            if hit is None or abstract is None:
                self._chain_costs[key] = None
                return None
            codes = {"F": "Filter", "P": "Project", "A": "Aggregate",
                     "S": "Sort", "T": "TopN", "L": "Limit"}
            label = "→".join(
                codes.get(k[0], str(k[0])) for k in key[1]
            ) or "chain"
            program_catalog.CATALOG.register(
                key, source="local", label=label,
                resolver=program_catalog.aot_resolver(hit[0], abstract),
            )
        cost = program_catalog.CATALOG.cost(key)
        self._chain_costs[key] = cost
        return cost

    def _finalize_chain(self, chain, env, mask, n_live: int, out_layout):
        cols = [
            Column(
                out_layout.types[s],
                env[s][0],
                env[s][1],
                out_layout.dicts.get(s),
                out_layout.pools.get(s),
                out_layout.arrays.get(s),
            )
            for s in out_layout.names
        ]
        out = Page(list(out_layout.names), cols, mask)
        out.known_rows = n_live
        # chains ending in a sort emit live rows first (sort_perm
        # pushes dead rows last)
        out.packed = isinstance(chain[-1], (P.Sort, P.TopN))
        if pad_capacity(out.known_rows) < out.capacity:
            out = self._compact(out)
        return out

    def _run_chain_chunked(
        self, chain: list[P.PlanNode], page: Page, agg_i: int, chunk_rows: int
    ) -> Page:
        """Partial-aggregate fixed-size row chunks, concatenate the
        partial states, FINAL-combine (grace aggregation)."""
        from trino_tpu.plan.distribute import _split_aggregate

        partial, final = _split_aggregate(chain[agg_i])
        pre = chain[:agg_i]
        post = chain[agg_i + 1:]
        uniform = (
            shapes.enabled(self.session) and page.capacity > chunk_rows
        )
        partials = []
        for lo in range(0, page.capacity, chunk_rows):
            hi = min(lo + chunk_rows, page.capacity)
            if uniform and hi - lo < chunk_rows:
                # back the final window up to full width so every chunk
                # shares one shape (and one compiled partial program);
                # mask off the rows the previous chunk already covered
                sl = _slice_page(
                    page, page.capacity - chunk_rows, page.capacity
                )
                overlap = chunk_rows - (hi - lo)
                sl.mask = sl.mask.at[:overlap].set(False)
                sl.known_rows = None
                shapes.record_waste("agg-chunk", hi - lo, chunk_rows)
            else:
                sl = _slice_page(page, lo, hi)
            partials.append(self._run_chain(pre + [partial], sl))
        combined = _concat_pages(partials)
        return self._run_chain([final] + post, combined)

    # ---- expression evaluation ------------------------------------------

    def _layout(self, page: Page) -> ColumnLayout:
        return ColumnLayout(
            types={n: c.type for n, c in zip(page.names, page.columns)},
            dictionaries={
                n: c.dictionary for n, c in zip(page.names, page.columns)
            },
            array_pools={
                n: c.array_pool for n, c in zip(page.names, page.columns)
                if c.array_pool is not None
            },
        )

    def _layout_sig(self, page: Page) -> tuple:
        # dictionary identity is its CONTENT fingerprint, not id():
        # spool-read pages rebuild equal dictionaries per statement,
        # and id-keyed programs would never be shared across them
        return tuple(
            (
                n, repr(c.type),
                None if c.dictionary is None else c.dictionary.fingerprint,
                None if c.hash_pool is None else c.hash_pool.token,
                None if c.array_pool is None else c.array_pool.token,
                c.valid is not None,
            )
            for n, c in zip(page.names, page.columns)
        ) + (page.capacity,)

    def _env(self, page: Page) -> dict:
        return {n: (c.data, c.valid) for n, c in zip(page.names, page.columns)}

    def _eval(self, page: Page, expr: RowExpression):
        """Evaluate one expression over a page (eager path for join
        residuals etc.). Returns (data, valid, dictionary), data
        broadcast to the page capacity."""
        compiled = compile_expr(expr, self._layout(page))
        data, valid = compiled.fn(self._env(page))
        cap = page.capacity
        if jnp.ndim(data) == 0:
            data = jnp.broadcast_to(data, (cap,))
        if valid is not None and jnp.ndim(valid) == 0:
            valid = jnp.broadcast_to(valid, (cap,))
        return data, valid, compiled.dictionary

    # ---- leaf nodes ------------------------------------------------------

    def _RemoteSource(self, node: P.RemoteSource) -> Page:
        """Pages for remote sources arrive out-of-band (fleet tasks
        resolve spool partitions before execution — the analog of the
        ExchangeOperator's pulled pages, MAIN/operator/ExchangeOperator.java:43)."""
        pages = getattr(self, "remote_pages", None) or {}
        if node.source_id not in pages:
            raise RuntimeError(f"no pages bound for remote source {node.source_id!r}")
        return pages[node.source_id]

    def _TableScan(self, node: P.TableScan) -> Page:
        if node.split is not None:
            return self._scan_split(node)
        connector = self.metadata.connector(node.catalog)
        if node.domains and node.assignments and getattr(
            connector, "supports_domains", False
        ):
            # domain-pruned scans bypass the device cache (the pruned
            # row set is filter-specific, not the table)
            return self._scan_pruned(node, connector)
        cache, columns = self._resident_columns(node, connector)
        # the whole table in the connector's order, live rows a prefix:
        # the one page that carries the declared sort order
        return Page(
            list(node.assignments), columns, cache[""],
            known_rows=cache["#rows"], packed=True,
            ordered_on=_declared_order(node, connector),
        )

    def _resident_columns(self, node: P.TableScan, connector):
        """The whole table's page dict (``scan_cache``'s shape: column
        key -> device Column, ``""`` the live mask, ``"#rows"``) with
        every column the scan assigns in it, and those columns in the
        scan's order. What is not resident yet is read from the
        connector and copied to the device under an ``upload`` span
        (its ``bytes``: what the span stored); a whole-table scan and a
        split scan of a cacheable connector share the one copy."""
        if not connector.cacheable:
            cache = {}  # live views (system tables) re-scan per query
        else:
            # process-wide shared pages: concurrent queries (and other
            # executors over the same connector) reuse one resident copy
            cache = scan_cache.SHARED.table(
                connector, node.schema, node.table
            )
        hashed_syms = set(node.hash_varchar or [])
        # hash-coded and dictionary-coded variants of a column cache
        # under distinct keys (a symbol's encoding is plan-dependent)
        def ckey(sym, cname):
            return f"#hash:{cname}" if sym in hashed_syms else cname

        missing = [
            (s, c) for s, c in node.assignments.items()
            if ckey(s, c) not in cache
        ]
        if missing or "" not in cache:
            # columns not resident yet: the connector's read and the
            # host->device copy
            with telemetry.child_span("upload", table=node.table) as span:
                cols = connector.scan(
                    node.schema, node.table, [c for _, c in missing]
                )
                if missing:
                    # row count from the scanned arrays themselves: a
                    # second row_count() call could see a DIFFERENT
                    # snapshot on live views (system tables)
                    first = cols[missing[0][1]]
                    n = len(
                        first[0] if isinstance(first, tuple) else first
                    )
                else:
                    n = connector.row_count(node.schema, node.table)
                cap = shapes.bucket(n, site="scan")
                uploaded = 0  # device bytes this span stores
                if "" not in cache:
                    mask = np.zeros(cap, dtype=np.bool_)
                    mask[:n] = True
                    cache[""] = jnp.asarray(mask)
                    uploaded += mask.nbytes
                for sym, cname in missing:
                    col = cache[ckey(sym, cname)] = _scan_column(
                        node.outputs[sym], cols[cname], cap,
                        hashed=sym in hashed_syms,
                    )
                    uploaded += scan_cache.column_nbytes(col)
                cache["#rows"] = n
                if span is not None:
                    span.attrs["bytes"] = int(uploaded)
            if connector.cacheable:
                scan_cache.SHARED.publish()
        return cache, [
            cache[ckey(s, c)] for s, c in node.assignments.items()
        ]

    def _scan_pruned(self, node: P.TableScan, connector) -> Page:
        """Scan with TupleDomain pushdown: the connector prunes storage
        units (parquet rowgroups) by footer stats; the filter above
        re-applies, so results stay exact (PushPredicateIntoTableScan +
        rowgroup pruning, lib/trino-parquet/.../reader/ParquetReader.java:85).

        With ``device_cache_enabled``, the pruned device page is pinned
        in the cross-query HBM tier keyed by connector fingerprint +
        assignments + the pushed domains (a pruned row set is
        filter-specific, so the domains ARE the key — this closes the
        historical cache bypass for domain-pushdown scans)."""
        from trino_tpu.connectors.base import ColumnDomain

        dkey = tokens = None
        if self._device_cache_on():
            from trino_tpu import cache as xcache

            hashed = set(node.hash_varchar or [])
            dkey = xcache.DEVICE.scan_key(
                connector, node.schema, node.table,
                tuple(
                    (s, c, s in hashed)
                    for s, c in node.assignments.items()
                ),
                domains=node.domains,
            )
            if dkey is not None:
                hit = xcache.DEVICE.get(dkey, self.cache_stats)
                if hit is not None:
                    return hit
                tokens = self._cache_tokens(
                    connector, node.schema, node.table
                )
        domains = {
            c: ColumnDomain(*dom) for c, dom in node.domains.items()
        }
        with telemetry.child_span("upload", table=node.table):
            cols = connector.scan(
                node.schema, node.table, list(node.assignments.values()),
                domains=domains,
            )
            page = self._scanned_page(node, cols, None)
        metrics = getattr(connector, "scan_metrics", None)
        if metrics:
            self.scan_log.append({
                "table": f"{node.schema}.{node.table}",
                "streamed": False,
                "rowgroups_total": int(metrics.get("rowgroups_total", 0)),
                "rowgroups_pruned": int(
                    metrics.get(
                        "rowgroups_pruned",
                        metrics.get("rowgroups_total", 0)
                        - metrics.get("rowgroups_read", 0),
                    )
                ),
                "partitions_pruned": int(
                    metrics.get("partitions_pruned", 0)
                ),
            })
            del self.scan_log[:-100]  # bounded: executors outlive queries
        if dkey is not None and tokens is not None:
            from trino_tpu import cache as xcache

            xcache.DEVICE.put(dkey, page, tokens, pool=self.memory_pool)
        return page

    def _scanned_page(self, node: P.TableScan, cols, split_rows) -> Page:
        """Host columns of an uncached scan onto the device, as a packed
        page. ``split_rows``: the split's row count (its capacity
        bucket, and its length where no column is read); None for a
        whole-table scan, sized by what came back."""
        if node.assignments:
            first = cols[next(iter(node.assignments.values()))]
            n = len(first[0] if isinstance(first, tuple) else first)
        else:
            n = split_rows
        cap = (
            shapes.bucket(n, site="scan") if split_rows is None
            else shapes.bucket(split_rows, site="scan-split")
        )
        hashed_syms = set(node.hash_varchar or [])
        names, columns = [], []
        for sym, cname in node.assignments.items():
            names.append(sym)
            columns.append(_scan_column(
                node.outputs[sym], cols[cname], cap,
                hashed=sym in hashed_syms,
            ))
        mask = np.zeros(cap, dtype=np.bool_)
        mask[:n] = True
        return Page(
            names, columns, jnp.asarray(mask), known_rows=n, packed=True,
        )

    def _scan_split(self, node: P.TableScan) -> Page:
        """Scan one row-range split of a table (fleet-mode source
        parallelism). Where the table may stay on the device — a
        cacheable connector, no pushed-down domain that prunes storage,
        a table that fits (``stream_scan.table_fits_resident``) — the
        split is a row range of the resident table
        (``_resident_split``): a worker holds its tables as the embedded
        runner does, and a warm task copies nothing from the host. Live
        views (system tables) and domain-pruned storage splits are read
        from the connector and uploaded, task by task."""
        from trino_tpu.connectors.base import ColumnDomain, Split
        from trino_tpu.exec import stream_scan

        start, count = node.split
        connector = self.metadata.connector(node.catalog)
        pruning = bool(node.domains) and getattr(
            connector, "supports_domains", False
        )
        if (
            connector.cacheable and not pruning
            and stream_scan.table_fits_resident(self, node)
        ):
            return self._resident_split(node, connector, start, count)
        kw = {}
        if pruning:
            # pushed-down domains (static filters + coordinator-fed
            # dynamic filters) prune row groups WITHIN this split; the
            # filter above re-applies, so dropped rows stay exact
            kw["domains"] = {
                c: ColumnDomain(*dom) for c, dom in node.domains.items()
            }
        with telemetry.child_span("upload", table=node.table):
            cols = connector.scan(
                node.schema, node.table, list(node.assignments.values()),
                split=Split(node.table, start, count), **kw,
            )
            return self._scanned_page(node, cols, count)

    def _resident_split(
        self, node: P.TableScan, connector, start: int, count: int
    ) -> Page:
        """Rows ``[start, start + count)`` of the resident table as a
        packed page at the split's capacity bucket: one ``scan_split``
        program slices every column (device work; dictionaries, hash
        pools and array pools are the whole table's, so the codes are
        too). Row for row what ``connector.scan(..., split=...)``
        uploads. Only this path opens a ``split-scan`` span:
        ``resident_split_scans`` on the statement's row counts them, and
        ``trino_resident_split_scans_total`` the process's."""
        with telemetry.child_span("split-scan", table=node.table):
            cache, columns = self._resident_columns(node, connector)
            n = max(0, min(count, cache["#rows"] - start))
            cap = shapes.bucket(count, site="scan-split")
            sig = tuple(
                (str(c.data.dtype), c.data.shape[1:], c.valid is not None)
                for c in columns
            ) + (cache[""].shape[0],)
            key = ("scan_split", sig, start, n, cap)
            fn = self._jit_cache.get(key)
            with _dispatching("scan_split", fn is None) as dispatch:
                if fn is None:
                    def split_fn(arrays):
                        return K.slice_rows(arrays, start, n, cap)

                    fn = _named_jit(split_fn, "scan_split")
                    self._jit_cache[key] = fn
                arrays, mask = fn([(c.data, c.valid) for c in columns])
                dispatch.note(rows_out=n, columns=len(columns))
        telemetry.RESIDENT_SPLIT_SCANS.inc(table=node.table)
        return Page(
            list(node.assignments),
            [dc_replace(c, data=d, valid=v)
             for c, (d, v) in zip(columns, arrays)],
            mask, known_rows=n, packed=True,
        )

    def _Exchange(self, node: P.Exchange) -> Page:
        # single-device execution: every exchange is the identity (the
        # mesh executor overrides this with collectives/gathers)
        return self.execute(node.source)

    def _Values(self, node: P.Values) -> Page:
        from trino_tpu.page import StringDictionary

        n = len(node.rows)
        cap = shapes.bucket(max(n, 8), site="values")
        mask = np.zeros(cap, dtype=np.bool_)
        mask[:n] = True
        names, cols = [], []
        for i, (sym, t) in enumerate(node.outputs.items()):
            vals = [r[i] for r in node.rows]
            if isinstance(t, (T.ArrayType, T.MapType, T.RowType)):
                # pool-backed literals: from_numpy builds the pool and
                # the NULL mask from the None entries
                names.append(sym)
                cols.append(Column.from_numpy(t, vals, capacity=cap))
                continue
            nulls = np.asarray([v is None for v in vals], dtype=np.bool_)
            filled = [0 if v is None else v for v in vals]
            dictionary = None
            if isinstance(t, T.VarcharType):
                dictionary, codes = StringDictionary.from_strings(
                    np.asarray(
                        ["" if nulls[j] else str(v)
                         for j, v in enumerate(filled)] or [""],
                        dtype=object,
                    )
                )
                data = np.zeros(cap, dtype=np.int32)
                data[:n] = codes[:n]
            elif isinstance(t, T.DecimalType) and t.is_long:
                data = np.zeros((cap, 2), dtype=np.int64)
                iv = np.asarray(filled, dtype=np.int64)
                data[:n, 0] = iv >> 32
                data[:n, 1] = iv & 0xFFFFFFFF
            else:
                data = np.zeros(cap, dtype=t.np_dtype)
                data[:n] = np.asarray(filled, dtype=t.np_dtype)
            valid = None
            if nulls.any():
                v = np.ones(cap, dtype=np.bool_)
                v[:n] = ~nulls
                valid = jnp.asarray(v)
            names.append(sym)
            cols.append(Column(t, jnp.asarray(data), valid, dictionary))
        return Page(
            names, cols, jnp.asarray(mask),
            known_rows=n, packed=True,
        )

    # ---- write path ------------------------------------------------------

    def _TableWriter(self, node: "P.TableWriter") -> Page:
        """Drain the upstream subtree into a connector WriteSink
        (MAIN/operator/TableWriterOperator.java analog). Emits one row
        per sealed fragment — ($rows, $bytes, $fragment) — with the
        task totals on the first row; an empty input emits zero rows
        (TableFinish still commits, so an empty CTAS creates the
        table)."""
        from trino_tpu.exec import write as W
        from trino_tpu.exec.spool import page_to_host

        page = self.execute(node.source)
        handle = node.handle
        conn = self.metadata.connector(handle["catalog"])
        sink = conn.write_sink(handle, getattr(self, "write_ctx", None))
        mctx = self.memory_ctx.child("table-writer")
        try:
            W.write_through_sink(
                sink, handle, page_to_host(page), node.columns, mctx,
            )
            res = W.finish_sink(sink, mctx)
        except BaseException:
            sink.abort()
            raise
        #: harvested into task stats by the worker / EXPLAIN ANALYZE
        self.last_write_stats = res
        rows = [
            (
                res["rows_written"] if i == 0 else 0,
                res["bytes_written"] if i == 0 else 0,
                f,
            )
            for i, f in enumerate(res["fragments"])
        ]
        return self._Values(P.Values(dict(node.outputs), rows=rows))

    def _TableFinish(self, node: "P.TableFinish") -> Page:
        """Single-task atomic commit (TableFinishOperator analog):
        collect the gathered fragment rows and hand them to
        Connector.finish_write exactly once."""
        from trino_tpu.exec import write as W
        from trino_tpu.exec.spool import page_to_host

        page = self.execute(node.source)
        frags = W.fragment_rows(page_to_host(page))
        token = str(
            (getattr(self, "write_ctx", None) or {}).get("epoch", "")
        )
        rows, secs = W.commit_write(
            self.metadata, node.handle, frags, token=token,
        )
        h = node.handle
        self.invalidate_scan(h["catalog"], h["schema"], h["table"])
        summary = W.fragments_summary(frags)
        self.last_commit_stats = {
            "rows": rows,
            "bytes": summary["bytes"],
            "files": summary["files"],
            "commit_seconds": secs,
        }
        return self._Values(P.Values(dict(node.outputs), rows=[(rows,)]))

    # ---- row-level nodes -------------------------------------------------

    def _Output(self, node: P.Output) -> Page:
        self._defer_sync_for = node.source
        try:
            page = self.execute(node.source)
        finally:
            self._defer_sync_for = None
        cols = [page.column(s) for s in node.symbols]
        out = Page(
            list(node.names), cols, page.mask,
            known_rows=page.known_rows, packed=page.packed,
        )
        pend = getattr(page, "pending_flags", None)
        if pend is not None:
            out.pending_flags = pend
        return out

    def note_chain_flags(self, vals, caps_key, caps) -> bool:
        """Act on one chain run's flags, already on the host (read in
        the chain's own sync, or — a statement's last chain, deferred —
        with its result): a tripped overflow flag grows that
        Aggregate's table, a tripped order check
        (``stage.unordered_flag``) bars the chain from grouping by
        runs. Both are remembered per chain shape. True: the rows of
        this run are not the answer, run the chain (the query) again."""
        tripped = [k for k, v in vals.items() if v]
        if not tripped:
            return False
        if any(k < 0 for k in tripped):
            # a run's rows under a broken order mean nothing, its
            # overflow flag included: rerun by sort, table as it was
            self._jit_cache[_unordered_key(caps_key)] = True
            telemetry.STREAMED_GROUPBY_FALLBACKS.inc()
            return True
        for i in tripped:
            cap, mx = caps[i]
            if cap >= mx:
                raise RuntimeError(
                    "aggregation table overflow at max capacity"
                )
            caps[i][0] = min(cap * 8, mx)
        self._jit_cache[caps_key] = {i: list(v) for i, v in caps.items()}
        return True

    def _compact(self, page: Page, extra_capacity: int = 0) -> Page:
        """Gather live rows to the front and shrink capacity
        (Page.compact analog, SPI/Page.java:180) — one jitted program
        per (layout, capacity) so the device sees a single dispatch.
        Syncs the count only when the producer did not record it."""
        n_live = page.num_rows()
        cap = shapes.bucket(n_live + extra_capacity, site="compact")
        if page.packed and cap >= page.capacity:
            return page
        limit = cap if cap < page.capacity else page.capacity
        if shapes.enabled(self.session):
            # positional key: any two pages with the same dtype/lane/
            # nullability vector share one gather program, whatever
            # their column names
            sig = tuple(
                (str(c.data.dtype), c.data.shape[1:], c.valid is not None)
                for c in page.columns
            ) + (page.capacity,)
            keys = [str(i) for i in range(len(page.columns))]
        else:
            sig = self._layout_sig(page)
            keys = list(page.names)
        key = ("compact", sig, limit)
        fn = self._jit_cache.get(key)
        with _dispatching("compact", fn is None) as dispatch:
            if fn is None:
                def compact_fn(env, mask):
                    return K.compact_rows(env, mask, limit)

                fn = _named_jit(compact_fn, "compact")
                self._jit_cache[key] = fn
            env_in = {
                k: (c.data, c.valid) for k, c in zip(keys, page.columns)
            }
            env2, mask2 = fn(env_in, page.mask)
            dispatch.note(
                rows_in=page.capacity, rows_out=limit,
                columns=len(page.columns),
                gather_ops=K.gather_plan(
                    (c.data.dtype, c.data.shape[1:], c.valid is not None)
                    for c in page.columns
                )[1],
            )
        cols = [
            Column(c.type, *env2[k], c.dictionary, c.hash_pool, c.array_pool)
            for k, c in zip(keys, page.columns)
        ]
        out = Page(list(page.names), cols, mask2)
        out.known_rows = n_live
        out.packed = True
        return out

    # ---- aggregation -----------------------------------------------------

    def _Join(self, node: P.Join) -> Page:
        if node.kind == "right":
            node = P.Join(
                node.outputs, kind="left", left=node.right, right=node.left,
                criteria=[(r, l) for l, r in node.criteria],
                filter=node.filter,
                df_range_keep=None, df_keep_frac=None,
                key_ranges=node.key_ranges,
            )
        budget = self.hbm_budget()
        if budget and node.kind in ("inner", "left") and node.criteria:
            plan = self._plan_budget_join(node, budget)
            if plan is not None:
                return plan
        if not budget and node.kind in ("inner", "left") and node.criteria:
            plan = self._maybe_revoke_join(node)
            if plan is not None:
                return plan
        frag = self._build_cache_probe(node.right)
        right_hit = frag[2] if frag is not None else None
        if not budget and right_hit is None:
            # prefetch trades device memory for round trips — never
            # under an HBM budget, where spill paths may stream the
            # same subtrees chunk-wise instead (and never when the
            # build side is already HBM-resident: prefetching its scan
            # chains would pin pages the hit makes redundant)
            self._prefetch_join_chains(node)
        left = self._compact(self.execute(node.left))
        if right_hit is not None:
            right = right_hit
        else:
            right = self._compact(self.execute(node.right))
            if frag is not None:
                from trino_tpu import cache as xcache

                xcache.DEVICE.put(
                    frag[0], right, frag[1], pool=self.memory_pool
                )
        if node.kind == "cross":
            return self._cross_join(node, left, right)
        try:
            return self._equi_join(node, left, right)
        except memory.ExceededMemoryLimitError:
            # reactive revocation: the resident working set (padded
            # device capacities) breached the per-node cap even though
            # the live-row estimate fit. The failed reserve recorded
            # nothing, so re-plan the join through the spill tier.
            if budget or node.kind not in ("inner", "left") or not node.criteria:
                raise
            plan = self._maybe_revoke_join(node, force=True)
            if plan is None:
                raise
            return plan

    def _build_cache_probe(self, sub: P.PlanNode):
        """``(key, tokens, hit_page|None)`` when a join build-side
        subtree is fragment-cacheable in the HBM tier (keyed by the
        canonical subtree hash — the *built* pages, dictionaries
        unified and compacted, are what gets pinned); None otherwise."""
        if not self._device_cache_on():
            return None
        from trino_tpu import cache as xcache

        digest = xcache.plan_digest(sub, self.session)
        if digest is None:
            return None
        tokens = xcache.table_tokens(sub, self.metadata)
        if tokens is None or not tokens:
            # no table scans under the subtree (Values/RemoteSource):
            # nothing content-addresses its data, so never cache it
            return None
        key = xcache.DEVICE.frag_key(digest)
        return key, tokens, xcache.DEVICE.get(key, self.cache_stats)

    def _estimated_selectivity(self, node: P.Filter) -> float | None:
        """The planner's estimate of the fraction of rows ``node``
        keeps (plan/stats.py, column statistics), None without one."""
        from trino_tpu.plan import stats as plan_stats

        if getattr(node, "source", None) is None:
            return None
        src = plan_stats.estimate(node.source, self.metadata)
        if not src.rows > 0:
            return None
        return plan_stats.estimate(node, self.metadata).rows / src.rows

    def _prefetch_join_chains(self, node: P.PlanNode) -> None:
        """Dispatch every aggregate-free Filter/Project chain over a
        table scan found under a join tree in one async burst, then
        fetch ALL their live counts in a single host round trip.

        The per-chain sync exists to learn the live count (capacity
        decisions); issuing them serially pays one device round trip
        per chain (Q3: three scan chains = three syncs; their cost is
        not measured on this machine). Independent chains have no data
        dependencies, so their
        programs queue back-to-back and one transfer collects every
        count (the reference overlaps the same work with concurrent
        split drivers, MAIN/execution/executor/)."""
        cands = []

        def chain_of(n):
            chain = []
            cur = n
            while isinstance(cur, stage.FUSABLE):
                chain.append(cur)
                cur = cur.sources[0]
            return list(reversed(chain)), cur

        def collect(n):
            if isinstance(n, stage.FUSABLE):
                chain, base = chain_of(n)
                if (
                    isinstance(base, P.TableScan)
                    and base.split is None
                    and all(
                        isinstance(x, (P.Filter, P.Project))
                        for x in chain
                    )
                ):
                    cands.append((n, chain, base))
                return
            if isinstance(n, (P.Join, P.SemiJoin)):
                for s in n.sources:
                    collect(s)

        for s in node.sources:
            collect(s)
        cands = [c for c in cands if id(c[0]) not in self._prefetched]
        if len(cands) < 2:
            return  # batching needs at least two chains to pay off
        pending = []
        for top, chain, scan in cands:
            base = self._TableScan(scan)
            env, mask, flags, n_live_dev, out_layout = self._dispatch_chain(
                chain, base, {}
            )
            pending.append((top, chain, env, mask, n_live_dev, out_layout))
        with telemetry.child_span("host_sync", site="prefetch_counts"):
            counts = jax.device_get([p[4] for p in pending])
        for (top, chain, env, mask, _d, out_layout), n_live in zip(
            pending, counts
        ):
            page = self._finalize_chain(
                chain, env, mask, int(n_live), out_layout
            )
            self._prefetched[id(top)] = (top, page)

    def _plan_budget_join(self, node: P.Join, budget: int) -> Page | None:
        """Memory-scaled join strategies (SURVEY §5.7): streamed probe
        against a resident build when only the probe exceeds budget,
        grace-hash partitioning when both sides do. Returns None when
        the resident path fits."""
        from trino_tpu.exec import spill

        l_bytes = spill.est_output_bytes(self, node.left)
        r_bytes = spill.est_output_bytes(self, node.right)
        slab = budget // 4
        if l_bytes <= slab and r_bytes <= slab:
            return None
        probe_chain, probe_scan = self._streamable(node.left)
        if l_bytes > slab and r_bytes <= slab and probe_scan is not None:
            build = self._compact(self.execute(node.right))
            return spill.streamed_probe_join(
                self, node, probe_chain, probe_scan, build
            )
        if l_bytes > slab or r_bytes > slab:
            # grace-hash handles inner AND left joins (each partition
            # pair covers its key range exclusively, so unmatched
            # probe rows emit exactly once)
            return spill.grace_join(self, node)
        return None

    def _maybe_revoke_join(self, node: P.Join, force: bool = False) -> Page | None:
        """Memory revocation (MemoryRevokingScheme analog): with no
        session hbm budget, a hash join whose estimated resident
        working set would breach query_max_memory_per_node is switched
        into the spill tier — the cap stands in as the budget for the
        whole subtree — instead of failing at reservation time.
        ``force`` skips the estimate check: the reactive path in
        ``_Join`` uses it after a resident reserve already raised
        (padded device capacities can exceed the live-row estimate).
        Only when even the revoked path cannot fit does the pool raise
        ExceededMemoryLimitError."""
        cap = self.memory_pool.limit_bytes()
        if not cap:
            return None
        from trino_tpu.exec import spill

        l_bytes = spill.est_output_bytes(self, node.left)
        r_bytes = spill.est_output_bytes(self, node.right)
        est = l_bytes + r_bytes + spill.est_output_bytes(self, node)
        if not force and est <= max(
            cap - self.memory_pool.reserved_bytes, 0
        ):
            return None
        if max(l_bytes, r_bytes) <= cap // 4:
            # both sides fit a slab of the cap: the spill tier has no
            # plan for this join (``_plan_budget_join``), it runs resident
            return None
        prev = self._revoked_budget
        self._revoked_budget = cap
        try:
            with telemetry.child_span(
                "join-revoked", estimated_bytes=int(est), cap_bytes=int(cap),
                forced=force,
            ):
                plan = self._plan_budget_join(node, cap)
            self.memory_revocations += 1
            telemetry.JOIN_REVOCATIONS.inc()
            return plan
        finally:
            self._revoked_budget = prev

    @staticmethod
    def _streamable(node: P.PlanNode):
        """(chain, scan) when the subtree is a fusable chain over a
        TableScan — the shape the chunked scan path can stream."""
        chain: list[P.PlanNode] = []
        cur = node
        while isinstance(cur, stage.FUSABLE):
            chain.append(cur)
            cur = cur.sources[0]
        if isinstance(cur, P.TableScan):
            # only row-local operators chunk safely here: aggregates
            # reduce cardinality (and stream independently via
            # execute()); Limit/TopN/Sort are global — a per-chunk
            # limit concatenated across chunks would drop the
            # truncation (run_chain_streamed handles those shapes via
            # its partial/final split instead)
            if any(
                isinstance(n, (P.Aggregate, P.Limit, P.TopN, P.Sort))
                for n in chain
            ):
                return None, None
            return list(reversed(chain)), cur
        return None, None

    #: cross joins materialize chunk-wise beyond this many output rows
    #: (previously a moderately sized cross join OOMed in one shot)
    CROSS_CHUNK_ROWS = 1 << 22

    def _cross_join(self, node: P.Join, left: Page, right: Page) -> Page:
        # callers (_Join) hand in already-compacted pages
        n_l, n_r = left.num_rows(), right.num_rows()
        from trino_tpu import session_properties as SP

        limit = int(SP.get(self.session, "cross_join_chunk_rows"))
        budget = self.hbm_budget()
        if budget:
            from trino_tpu.exec import spill

            limit = min(
                limit,
                max(
                    (budget // spill.CHUNK_BUDGET_FRACTION)
                    // spill.row_bytes(node.outputs),
                    1 << 16,
                ),
            )
        if n_l * n_r > limit and max(n_l, n_r) > 1:
            from trino_tpu.exec import spill

            # chunk the LARGER side: chunking the left against a
            # right that alone exceeds the limit would recurse with a
            # 1-row chunk forever
            chunk_left = n_l >= n_r
            n_big = n_l if chunk_left else n_r
            n_other = n_r if chunk_left else n_l
            rows_per = max(limit // max(n_other, 1), 1)
            runs = []
            for lo in range(0, n_big, rows_per):
                hi = min(lo + rows_per, n_big)
                if chunk_left:
                    out = self._cross_join(
                        node, self._compact(_slice_page(left, lo, hi)),
                        right,
                    )
                else:
                    out = self._cross_join(
                        node, left,
                        self._compact(_slice_page(right, lo, hi)),
                    )
                run = spill.page_to_host(self._compact(out))
                if run.n_rows:
                    runs.append(run)
            if not runs:
                runs = [spill._empty_run(node.outputs)]
            return spill.host_concat_to_page(self, runs)
        cap = shapes.bucket(max(n_l * n_r, 1), site="cross-join")
        key = (
            "cross", n_l, n_r,
            self._layout_sig(left), self._layout_sig(right),
        )
        fn = self._jit_cache.get(key)
        with _dispatching("cross_join", fn is None):
            if fn is None:
                l_cap, r_cap = left.capacity, right.capacity
                lnames, rnames = list(left.names), list(right.names)

                def fx(lenv, renv):
                    j = jnp.arange(cap)
                    li = jnp.clip(j // max(n_r, 1), 0, max(l_cap - 1, 0))
                    ri = jnp.clip(j % max(n_r, 1), 0, max(r_cap - 1, 0))
                    out_live = j < n_l * n_r
                    env2 = {}
                    for names, env, idx in (
                        (lnames, lenv, li), (rnames, renv, ri)
                    ):
                        for nm in names:
                            env2[nm] = K.rows_at(*env[nm], idx)
                    return env2, out_live

                fn = _named_jit(fx, "cross_join")
                self._jit_cache[key] = fn
            env2, mask = fn(self._env(left), self._env(right))
        names, cols = [], []
        for page in (left, right):
            for nm, c in zip(page.names, page.columns):
                names.append(nm)
                cols.append(Column(c.type, *env2[nm], c.dictionary, c.hash_pool, c.array_pool))
        out = Page(names, cols, mask)
        out.known_rows = n_l * n_r
        out.packed = True
        return out

    def _nested_loop_join(self, node: P.Join, probe: Page, build: Page) -> Page:
        """Joins WITHOUT equi criteria (`a JOIN b ON a.x < b.y`): the
        NestedLoopJoinOperator + join-filter shape
        (MAIN/operator/join/NestedLoopJoinOperator.java:43). Cross-
        expand chunk-wise (bounded by CROSS_CHUNK_ROWS), evaluate the
        filter over the pair page, and for outer kinds append the
        unmatched rows with a NULL far side. _Join already flipped
        RIGHT to LEFT, so kinds here are inner/left/full."""
        from trino_tpu.exec import spill

        # row-id lanes ride through the cross expansion so unmatched
        # probe/build rows are identifiable afterwards
        def with_ids(page: Page, idname: str) -> Page:
            ids = Column(
                T.BIGINT,
                jnp.arange(page.capacity, dtype=jnp.int64),
            )
            return Page(
                list(page.names) + [idname], list(page.columns) + [ids],
                page.mask, known_rows=page.known_rows, packed=page.packed,
            )

        p2 = with_ids(probe, "__nl_pid")
        b2 = with_ids(build, "__nl_bid")
        cross_outputs = {
            **{n: c.type for n, c in zip(p2.names, p2.columns)},
            **{n: c.type for n, c in zip(b2.names, b2.columns)},
        }
        cross_node = P.Join(
            cross_outputs, kind="cross", left=node.left, right=node.right
        )
        pairs = self._cross_join(cross_node, p2, b2)
        if node.filter is not None:
            ce = compile_expr(node.filter, self._layout(pairs))
            data, valid = ce.fn(self._env(pairs))
            keep = data if valid is None else (data & valid)
            pairs = self._compact(
                Page(list(pairs.names), list(pairs.columns),
                     pairs.mask & keep)
            )
        out_syms = list(node.outputs)
        matched = Page(
            out_syms, [pairs.column(s) for s in out_syms], pairs.mask,
            known_rows=pairs.known_rows, packed=pairs.packed,
        )
        if node.kind == "inner":
            return matched
        runs = [spill.page_to_host(matched)]
        pid_run = spill.page_to_host(
            Page(["__nl_pid"], [pairs.column("__nl_pid")], pairs.mask,
                 known_rows=pairs.known_rows, packed=pairs.packed)
        )
        matched_pids = set(pid_run.columns[0][0].tolist())
        runs.append(self._nl_unmatched(
            node, probe, build, matched_pids, out_syms, probe_side=True
        ))
        if node.kind == "full":
            bid_run = spill.page_to_host(
                Page(["__nl_bid"], [pairs.column("__nl_bid")], pairs.mask,
                     known_rows=pairs.known_rows, packed=pairs.packed)
            )
            matched_bids = set(bid_run.columns[0][0].tolist())
            runs.append(self._nl_unmatched(
                node, probe, build, matched_bids, out_syms,
                probe_side=False,
            ))
        runs = [r for r in runs if r.n_rows] or [
            spill._empty_run(dict(node.outputs))
        ]
        return spill.host_concat_to_page(self, runs)

    def _nl_unmatched(
        self, node: P.Join, probe: Page, build: Page, matched: set,
        out_syms: list, probe_side: bool,
    ):
        """HostRun of one side's unmatched rows, far side all-NULL."""
        from trino_tpu.exec import spill

        page = probe if probe_side else build
        run = spill.page_to_host(page)
        keep = [
            i for i in range(run.n_rows) if i not in matched
        ]
        near = set(page.names)
        cols = []
        types = []
        for s in out_syms:
            t = node.outputs[s]
            types.append(t)
            if s in near:
                v, valid = run.columns[run.names.index(s)]
                cols.append((
                    v[keep],
                    None if valid is None else valid[keep],
                ))
            else:
                # far side: typed zeros, all invalid
                src = (build if probe_side else probe).column(s)
                shape = (len(keep), 2) if jnp.ndim(src.data) == 2 else (
                    len(keep),
                )
                filler = (
                    np.zeros(len(keep), dtype=object)
                    if src.dictionary is not None or src.hash_pool is not None
                    else np.zeros(shape, dtype=t.np_dtype)
                )
                if filler.dtype == object:
                    filler[:] = ""
                cols.append((filler, np.zeros(len(keep), dtype=bool)))
        return spill.HostRun(out_syms, types, cols, len(keep))

    def _unify_join_dicts(self, probe: Page, build: Page, criteria):
        """Remap VARCHAR key pairs onto shared dictionaries (host-side
        dictionary union + one device gather per remapped column).
        Hash-coded pairs skip remapping entirely — hash codes are
        globally consistent — but must pass the cross-pool injectivity
        proof so hash equality implies string equality."""
        for lsym, rsym in criteria:
            pc, bc = probe.column(lsym), build.column(rsym)
            if pc.hash_pool is not None and bc.hash_pool is not None:
                pc.hash_pool.verify_joinable(bc.hash_pool)
                continue
            if pc.dictionary is not None or bc.dictionary is not None:
                pc2, bc2 = unify_dictionaries(pc, bc)
                probe.columns[probe.names.index(lsym)] = pc2
                build.columns[build.names.index(rsym)] = bc2

    @staticmethod
    def _join_key_kinds(probe, build, criteria):
        """Static per-criterion key kind from the page columns:
        'hash' = hash-coded varchar (key is the hash lane alone; the id
        lane is row identity), 'auto' = plain/limb columns."""
        kinds = []
        for l, r in criteria:
            pc = probe.column(l)
            bc = build.column(r)
            if pc.hash_pool is not None or bc.hash_pool is not None:
                if pc.hash_pool is None or bc.hash_pool is None:
                    raise NotImplementedError(
                        "join between hash-coded and dictionary-coded "
                        "varchar (the planner co-encodes join pairs)"
                    )
                kinds.append("hash")
            else:
                kinds.append("auto")
        return kinds

    @staticmethod
    def _join_key_width(key_ranges, criteria, probe, build):
        """``(lo, key_bits)`` — static — for the combined key of a join:
        where the join is on ONE fixed-width integer criterion whose
        exact range ``(lo, hi)`` the plan proves (``Join.key_ranges``,
        under either orientation of the pair), the origin both sides
        are shifted to and ``bit_length(hi - lo)``, the width
        ``kernels.join_ranges`` ranks them at; for anything else — a
        multi-column (hashed, verified) key, a two-limb decimal, a
        float, a dictionary- or hash-coded varchar, a criterion the
        plan proved nothing of — ``(0, 64)``: the key as it is."""
        if not key_ranges or len(criteria) != 1:
            return 0, 64
        (l, r), = criteria
        rng = key_ranges.get((l, r)) or key_ranges.get((r, l))
        if rng is None:
            return 0, 64
        for col in (probe.column(l), build.column(r)):
            if (
                col.dictionary is not None or col.hash_pool is not None
                or jnp.ndim(col.data) != 1
                or np.dtype(col.data.dtype).kind != "i"
            ):
                return 0, 64
        lo, hi = rng
        bits = max(1, int(hi - lo).bit_length())
        return (lo, bits) if bits < 64 else (0, 64)

    @staticmethod
    def _traced_join_keys(penv, benv, criteria, kinds=None, lo=0):
        """Combined uint64 keys for probe/build sides from traced envs.

        Single fixed-width key -> exact; multi-column (including
        two-limb decimal keys, which expand into hi/lo parts) ->
        hash-combined and ``verify`` is True (matches re-checked after
        expansion). Hash-coded varchar keys ('hash' kind) contribute
        their hash lane only — the id lane is row identity, not value
        identity. The returned ``pairs`` are 1D (probe, build) part
        arrays for the verification loop. ``lo`` (``_join_key_width``)
        is the origin a single integer key is shifted to, as
        ``stage._shift_key`` shifts a group key: a bijection on the
        proven range, whose live keys then lie in ``[0, hi - lo]``; a
        dead or NULL row may wrap.
        """
        pv = bv = None
        p_parts: list = []
        b_parts: list = []
        for i, (l, r) in enumerate(criteria):
            pd, pvd = penv[l]
            bd, bvd = benv[r]
            pv = _and_mask(pv, pvd)
            bv = _and_mask(bv, bvd)
            if kinds is not None and kinds[i] == "hash":
                p_parts.append(pd[:, 0])
                b_parts.append(bd[:, 0])
            else:
                p_parts.extend(K.limb_parts(pd))
                b_parts.extend(K.limb_parts(bd))
        if len(p_parts) == 1:
            pk, _ = K.normalize_key(_shifted(p_parts[0], lo), None)
            bk, _ = K.normalize_key(_shifted(b_parts[0], lo), None)
            verify = False
        else:
            pk = K.hash_columns([(d, None) for d in p_parts])
            bk = K.hash_columns([(d, None) for d in b_parts])
            verify = True
        pairs = list(zip(p_parts, b_parts))
        return pk, bk, pv, bv, pairs, verify

    def _join_count(
        self, criteria, probe: Page, build: Page, key_ranges=None,
        kind: str = "inner",
    ):
        """Join phase A: sorted build order + per-probe match ranges +
        total match count — ONE jitted program, one host sync (the
        output-capacity decision, the reference's build-side barrier).
        ``key_ranges``: the plan node's (``Join.key_ranges``), from
        which the program takes its key's origin and width — both part
        of the program's cache key: another range, another program.
        """
        crit = list(criteria)
        key_lo, key_bits = self._join_key_width(
            key_ranges, crit, probe, build
        )
        key = (
            "joinA", tuple(criteria), key_lo, key_bits,
            self._layout_sig(probe), self._layout_sig(build),
        )
        fn = self._jit_cache.get(key)
        with _dispatching("join_count", fn is None) as dispatch:
            dispatch.note_join(build.capacity, key_bits, kind)
            if fn is None:
                kinds = self._join_key_kinds(probe, build, crit)

                def fa(penv, pmask, benv, bmask):
                    pk, bk, pv, bv, _, _ = self._traced_join_keys(
                        penv, benv, crit, kinds, key_lo
                    )
                    probe_live = pmask if pv is None else (pmask & pv)
                    build_live = bmask if bv is None else (bmask & bv)
                    order, lo, cnt = K.join_ranges(
                        bk, build_live, pk, probe_live, key_bits=key_bits
                    )
                    return order, lo, cnt, K.blocked_sum(cnt)

                fn = _named_jit(fa, "join_count")
                self._jit_cache[key] = fn
            order, lo, cnt, total_dev = fn(
                self._env(probe), probe.mask, self._env(build), build.mask
            )
        with telemetry.child_span("host_sync", site="join_total"):
            total = int(jax.device_get(total_dev))
        return order, lo, cnt, total

    # ---- dynamic filtering (DynamicFilterService analog,
    # MAIN/server/DynamicFilterService.java:106: collect build-side key
    # bounds, prune the probe before the expensive join work) ----------

    #: probes below this skip dynamic filtering (the two extra syncs
    #: cost more than the saved sort time)
    DF_MIN_PROBE = 1 << 17
    #: apply the filter only when it drops at least this fraction
    DF_MIN_DROP = 0.3

    def _df_pairs(self, criteria, probe: Page, build: Page):
        """Criteria usable for min/max dynamic filtering: plain integer
        domains (ints, dates, decimals, dictionary codes are excluded —
        code spaces already unified but bounds are meaningless across
        remaps)."""
        pairs = []
        for ls, rs in criteria:
            pc, bc = probe.column(ls), build.column(rs)
            if pc.dictionary is not None or bc.dictionary is not None:
                continue
            if pc.hash_pool is not None or bc.hash_pool is not None:
                continue  # hashes carry no order; min/max cannot prune
            if jnp.ndim(pc.data) != 1:
                continue  # two-limb columns have no 1D order domain
            if np.dtype(pc.data.dtype).kind != "i":
                continue
            pairs.append((ls, rs))
        return pairs

    def _dynamic_filter(self, node: P.Join, probe: Page, build: Page) -> Page:
        """Prune probe rows whose key cannot match any build row.

        Inner joins only: outer probes must keep unmatched rows. Cost:
        one tiny reduction program + one filtered compaction — two host
        syncs, the price the reference pays for its DF barrier. NULL
        probe keys are dropped too (they never match an inner join).

        Gated by the planner's df_range_keep hint: a min/max filter
        only prunes when the build's key RANGE is narrower than the
        probe's — uniform dense builds keep ~100% and the two syncs
        are pure cost (the measured Q3 regression)."""
        from trino_tpu import session_properties as SP

        if not SP.get(self.session, "dynamic_filtering_enabled"):
            return probe
        if node.kind != "inner" or probe.capacity < self.DF_MIN_PROBE:
            return probe
        if node.df_range_keep is None or node.df_range_keep > 0.7:
            return probe
        pairs = self._df_pairs(node.criteria, probe, build)
        if not pairs:
            return probe
        key_a = ("dfA", tuple(r for _, r in pairs), self._layout_sig(build))
        fn_a = self._jit_cache.get(key_a)
        with _dispatching("join_bounds", fn_a is None):
            if fn_a is None:
                rsyms = [r for _, r in pairs]

                def fa(benv, bmask):
                    outs = []
                    for r in rsyms:
                        d, v = benv[r]
                        live = bmask if v is None else (bmask & v)
                        big = jnp.iinfo(d.dtype).max
                        small = jnp.iinfo(d.dtype).min
                        outs.append(jnp.min(jnp.where(live, d, big)))
                        outs.append(jnp.max(jnp.where(live, d, small)))
                    return jnp.stack([o.astype(jnp.int64) for o in outs])

                fn_a = _named_jit(fa, "join_bounds")
                self._jit_cache[key_a] = fn_a
            bounds = fn_a(self._env(build), build.mask)
        key_b = ("dfB", tuple(l for l, _ in pairs), self._layout_sig(probe))
        fn_b = self._jit_cache.get(key_b)
        with _dispatching("join_bounds", fn_b is None):
            if fn_b is None:
                lsyms = [l for l, _ in pairs]

                def fb(penv, pmask, bnds):
                    keep = pmask
                    for i, l in enumerate(lsyms):
                        d, v = penv[l]
                        lo = bnds[2 * i].astype(d.dtype)
                        hi = bnds[2 * i + 1].astype(d.dtype)
                        keep = keep & (d >= lo) & (d <= hi)
                        if v is not None:
                            keep = keep & v
                    return keep, K.count_true(keep)

                fn_b = _named_jit(fb, "join_bounds")
                self._jit_cache[key_b] = fn_b
            keep, kept_dev = fn_b(self._env(probe), probe.mask, bounds)
        in_rows = probe.num_rows()
        with telemetry.child_span("host_sync", site="join_bounds_kept"):
            kept = int(jax.device_get(kept_dev))
        self.df_log.append(
            {"rows_in": in_rows, "rows_kept": kept, "pairs": pairs}
        )
        del self.df_log[:-100]  # bounded: executors outlive queries
        if kept > (1.0 - self.DF_MIN_DROP) * in_rows:
            return probe
        filtered = Page(
            list(probe.names), list(probe.columns), keep,
            known_rows=kept,
        )
        return self._compact(filtered)

    def _equi_join(self, node: P.Join, probe: Page, build: Page) -> Page:
        if not node.criteria:
            return self._nested_loop_join(node, probe, build)
        self._unify_join_dicts(probe, build, node.criteria)
        probe = self._dynamic_filter(node, probe, build)
        order, lo, cnt, total = self._join_count(
            node.criteria, probe, build, node.key_ranges, node.kind
        )
        out_cap = shapes.bucket(max(total, 1), site="join")
        # reserve the join's whole device working set (probe + build +
        # expansion output + index arrays) against the memory pool —
        # the budget tier's tests rely on this being honest, and the
        # per-node cap is enforced here (ExceededMemoryLimitError when
        # even the revoked/spill path cannot fit)
        out_row = sum(
            (2 if jnp.ndim((probe if s in probe.names else build)
                           .column(s).data) == 2 else 1) * 8
            for s in node.outputs
        )
        working_set = (
            _page_dev_bytes(probe) + _page_dev_bytes(build)
            + out_cap * (out_row + 8)
        )
        ctx = self.memory_ctx.child("join")
        ctx.reserve(working_set)
        ctx.free(working_set)
        key = (
            "joinB", node.kind, tuple(node.criteria), tuple(node.outputs),
            repr(node.filter), out_cap,
            self._layout_sig(probe), self._layout_sig(build),
        )
        hit = self._jit_cache.get(key)
        with _dispatching("join_expand", hit is None):
            if hit is None:
                hit = self._build_join_expand(node, probe, build, out_cap)
                self._jit_cache[key] = hit
            fn, out_meta = hit
            env2, mask2 = fn(
                self._env(probe), probe.mask, self._env(build), build.mask,
                order, lo, cnt,
            )
        cols = [
            Column(t, *env2[s], d, hp, ap) for s, _fp, t, d, hp, ap in out_meta
        ]
        out = Page([s for s, *_ in out_meta], cols, mask2)
        if (
            node.kind == "inner"
            and node.filter is None
            and len(node.criteria) == 1
        ):
            # exact single-key expansion emits matches as a dense
            # prefix of length ``total`` — downstream never re-syncs
            out.known_rows = total
            out.packed = True
        return out

    def _build_join_expand(self, node: P.Join, probe: Page, build: Page, out_cap: int):
        """Join phase B: expansion, verification, output gathers,
        residual filter, and outer-row sections — ONE jitted program.
        Outer (left/full) unmatched rows are emitted as extra full-size
        sections with NULLs for the far side, exactly like the mesh
        executor — no data-dependent capacity, no extra sync."""
        criteria = list(node.criteria)
        kind = node.kind
        p_cap, b_cap = probe.capacity, build.capacity
        out_meta = []  # (sym, from_probe, type, dict, hash_pool, array_pool)
        for sym in node.outputs:
            from_probe = sym in probe.names
            c = (probe if from_probe else build).column(sym)
            out_meta.append(
                (sym, from_probe, c.type, c.dictionary, c.hash_pool,
                 c.array_pool)
            )
        filter_c = None
        fsyms: list[str] = []
        if node.filter is not None:
            filter_c = compile_expr(node.filter, _pair_layout(probe, build))
            fsyms = sorted(_expr_symbols(node.filter))
        probe_names = set(probe.names)

        kinds = self._join_key_kinds(probe, build, criteria)

        def fb(penv, pmask, benv, bmask, order, lo, cnt):
            pk, bk, pv, bv, pairs, verify = self._traced_join_keys(
                penv, benv, criteria, kinds
            )
            probe_idx, build_idx, out_live = K.expand_matches(
                order, lo, cnt, out_cap
            )
            if verify:
                for pd, bd in pairs:
                    pb, _ = K.normalize_key(pd, None)
                    bb, _ = K.normalize_key(bd, None)
                    out_live = out_live & K.keys_match(
                        pb, bb, probe_idx, build_idx
                    )
            inner = {}
            for sym, from_probe, _t, _d, _hp, _ap in out_meta:
                d, v = (penv if from_probe else benv)[sym]
                idx = probe_idx if from_probe else build_idx
                inner[sym] = K.rows_at(d, v, idx)
            if filter_c is not None:
                fenv = _gather_pair_env(
                    penv, benv, probe_names, fsyms,
                    probe_idx, build_idx, base=inner,
                )
                fd, fv = filter_c.fn(fenv)
                out_live = out_live & (fd if fv is None else (fd & fv))
            sections = {sym: [inner[sym]] for sym, *_ in out_meta}
            masks = [out_live]
            if kind in ("left", "full"):
                matched = K.range_any(cnt, out_live)
                unmatched = pmask & ~matched
                for sym, from_probe, _t, _d, _hp, _ap in out_meta:
                    if from_probe:
                        sections[sym].append(penv[sym])
                    else:
                        d0, _ = benv[sym]
                        # preserve trailing lanes (two-limb decimals,
                        # sketch states) in the NULL section
                        sections[sym].append((
                            jnp.zeros(
                                (p_cap,) + d0.shape[1:], dtype=d0.dtype
                            ),
                            jnp.zeros((p_cap,), dtype=jnp.bool_),
                        ))
                masks.append(unmatched)
            if kind == "full":
                bmatched = K.scatter_any(build_idx, out_live, b_cap)
                bunmatched = bmask & ~bmatched
                for sym, from_probe, _t, _d, _hp, _ap in out_meta:
                    if from_probe:
                        d0, _ = penv[sym]
                        sections[sym].append((
                            jnp.zeros(
                                (b_cap,) + d0.shape[1:], dtype=d0.dtype
                            ),
                            jnp.zeros((b_cap,), dtype=jnp.bool_),
                        ))
                    else:
                        sections[sym].append(benv[sym])
                masks.append(bunmatched)
            env2 = {}
            for sym, *_ in out_meta:
                env2[sym] = _concat_sections(sections[sym])
            mask2 = masks[0] if len(masks) == 1 else jnp.concatenate(masks)
            return env2, mask2

        return _named_jit(fb, "join_expand"), out_meta

    def _build_semi_expand(self, node: P.SemiJoin, source: Page, filt: Page, out_cap: int):
        """Semi-join expansion phase: verify hash-combined matches and
        apply the correlated residual filter, then reduce per-probe —
        ONE jitted program returning the match vector."""
        criteria = list(node.keys)
        filter_c = None
        fsyms: list[str] = []
        if node.filter is not None:
            filter_c = compile_expr(node.filter, _pair_layout(source, filt))
            fsyms = sorted(_expr_symbols(node.filter))
        probe_names = set(source.names)

        kinds = self._join_key_kinds(source, filt, criteria)

        def fb(penv, benv, order, lo, cnt):
            pk, bk, pv, bv, pairs, _verify = self._traced_join_keys(
                penv, benv, criteria, kinds
            )
            probe_idx, build_idx, out_live = K.expand_matches(
                order, lo, cnt, out_cap
            )
            for pd, bd in pairs:
                pb, _ = K.normalize_key(pd, None)
                bb, _ = K.normalize_key(bd, None)
                out_live = out_live & K.keys_match(
                    pb, bb, probe_idx, build_idx
                )
            if filter_c is not None:
                fenv = _gather_pair_env(
                    penv, benv, probe_names, fsyms, probe_idx, build_idx
                )
                fd, fv = filter_c.fn(fenv)
                out_live = out_live & (fd if fv is None else (fd & fv))
            return K.range_any(cnt, out_live)

        return _named_jit(fb, "semi_join")

    # ---- window / set operations -----------------------------------------

    def _GroupId(self, node: P.GroupId) -> Page:
        """Replicate the input once per grouping set with NULLed
        non-member keys + a set-id column (GroupIdOperator analog,
        MAIN/operator/GroupIdOperator.java) — one device concat of k
        masked copies; the aggregation above fuses over the result."""
        src = self.execute(node.source)
        k = len(node.grouping_sets)
        in_cap = src.capacity
        out_cap = shapes.bucket(k * in_cap, site="group-id")
        keyed = set(s for st in node.grouping_sets for s in st)
        pad = out_cap - k * in_cap

        def tile(pieces, fill):
            if pad:
                pieces = pieces + [
                    jnp.full((pad,) + pieces[0].shape[1:], fill,
                             dtype=pieces[0].dtype)
                ]
            return jnp.concatenate(pieces)

        names, cols = [], []
        for name, col in zip(src.names, src.columns):
            if name in keyed:
                valid_full = (
                    col.valid if col.valid is not None
                    else jnp.ones((in_cap,), dtype=jnp.bool_)
                )
                none = jnp.zeros((in_cap,), dtype=jnp.bool_)
                valid = tile(
                    [
                        valid_full if name in st else none
                        for st in node.grouping_sets
                    ],
                    False,
                )
            else:
                valid = (
                    None if col.valid is None
                    else tile([col.valid] * k, False)
                )
            data = tile([col.data] * k, 0)
            names.append(name)
            cols.append(
                Column(col.type, data, valid, col.dictionary, col.hash_pool)
            )
        names.append(node.id_symbol)
        cols.append(Column(
            T.BIGINT,
            tile(
                [
                    jnp.full((in_cap,), i, dtype=jnp.int64)
                    for i in range(k)
                ],
                0,
            ),
        ))
        mask = tile([src.mask] * k, False)
        rows = src.num_rows()
        return Page(names, cols, mask, known_rows=rows * k, packed=False)

    def _Unnest(self, node: P.Unnest) -> Page:
        """Static-fanout UNNEST (UnnestOperator analog,
        MAIN/operator/unnest/UnnestOperator.java): output position
        t = i * k + j holds element j of source row i — one reshape,
        no data-dependent shapes. Shorter zipped arrays NULL-pad.

        UNNEST over real ARRAY columns takes the pool-expansion path
        (_unnest_columns) — lengths are data-dependent there."""
        page = self.execute(node.source)
        if any(not isinstance(a, tuple) for a in node.arrays):
            return self._unnest_columns(node, page)
        k = max(len(a) for a in node.arrays)
        cap = page.capacity
        out_cap = cap * k
        key = (
            "unnest",
            tuple(tuple(repr(e) for e in a) for a in node.arrays),
            tuple(node.element_symbols),
            self._layout_sig(page),
        )
        hit = self._jit_cache.get(key)
        with _dispatching("unnest", hit is None):
            if hit is None:
                from trino_tpu.page import StringDictionary

                layout = self._layout(page)
                producers = []  # per arg: list of ('expr', c) | ('code', int)
                elem_dicts = []
                for a, sym in zip(node.arrays, node.element_symbols):
                    cs = [compile_expr(e, layout) for e in a]
                    t = node.outputs[sym]
                    if isinstance(t, T.VarcharType):
                        if all(
                            c.is_literal and c.dictionary is not None
                            for c in cs
                        ):
                            # one merged dictionary over the literal pool;
                            # each element becomes a constant code
                            merged = StringDictionary(np.unique(
                                np.concatenate(
                                    [c.dictionary.values for c in cs]
                                )
                            ))
                            producers.append([
                                (
                                    "code",
                                    int(np.searchsorted(
                                        merged.values,
                                        c.dictionary.values[0],
                                    )),
                                )
                                for c in cs
                            ])
                            elem_dicts.append(merged)
                            continue
                        dict_ids = {id(c.dictionary) for c in cs}
                        if len(dict_ids) != 1 or None in {
                            c.dictionary for c in cs
                        }:
                            raise NotImplementedError(
                                "UNNEST varchar elements must share one "
                                "dictionary or all be literals"
                            )
                        elem_dicts.append(cs[0].dictionary)
                    else:
                        elem_dicts.append(None)
                    producers.append([("expr", c) for c in cs])

                def fx(env, mask):
                    idx = jnp.arange(out_cap, dtype=jnp.int32) // k
                    env2 = {}
                    for s, (d, v) in env.items():
                        env2[s] = K.rows_at(d, v, idx)
                    for sym, prods in zip(node.element_symbols, producers):
                        t = node.outputs[sym]
                        cols = []
                        vals = []
                        for kind_, c in prods:
                            if kind_ == "code":
                                d = jnp.full((cap,), c, dtype=jnp.int32)
                                v = None
                            else:
                                d, v = stage._bcast(*c.fn(env), cap)
                            cols.append(d)
                            vals.append(
                                jnp.ones((cap,), dtype=jnp.bool_)
                                if v is None else v
                            )
                        stacked = jnp.stack(cols, axis=1)  # [cap, k_m]
                        svalid = jnp.stack(vals, axis=1)
                        k_m = stacked.shape[1]
                        if k_m < k:  # NULL-pad shorter zipped arrays
                            pad = jnp.zeros((cap, k - k_m), dtype=stacked.dtype)
                            stacked = jnp.concatenate([stacked, pad], axis=1)
                            svalid = jnp.concatenate(
                                [
                                    svalid,
                                    jnp.zeros(
                                        (cap, k - k_m), dtype=jnp.bool_
                                    ),
                                ],
                                axis=1,
                            )
                        env2[sym] = (
                            stacked.reshape(out_cap),
                            svalid.reshape(out_cap),
                        )
                    return env2, mask[idx]

                hit = (_named_jit(fx, "unnest"), elem_dicts)
                self._jit_cache[key] = hit
            fn, elem_dicts = hit
            env2, mask2 = fn(self._env(page), page.mask)
        names, cols = [], []
        for nm, c in zip(page.names, page.columns):
            names.append(nm)
            cols.append(Column(c.type, *env2[nm], c.dictionary, c.hash_pool, c.array_pool))
        for sym, d in zip(node.element_symbols, elem_dicts):
            names.append(sym)
            cols.append(Column(node.outputs[sym], *env2[sym], d))
        return Page(names, cols, mask2)

    def _unnest_columns(self, node: P.Unnest, page: Page) -> Page:
        """UNNEST over ARRAY-typed columns: row lengths come from the
        host pool (offsets+values layout), the expansion index builds
        host-side (np.repeat over live rows), source columns gather
        device-side by the uploaded index, and element columns build
        from pool slices (UnnestOperator over ArrayBlock,
        MAIN/operator/unnest/UnnestOperator.java:44). Multiple arrays
        zip; shorter ones NULL-pad (Trino semantics)."""
        mask = np.asarray(page.mask)
        sel = np.nonzero(mask)[0]
        args = []
        for a in node.arrays:
            if isinstance(a, tuple):
                raise NotImplementedError(
                    "mixing ARRAY literals and ARRAY columns in one "
                    "UNNEST is not supported"
                )
            if not isinstance(a, InputRef):
                raise NotImplementedError(
                    "UNNEST argument must be an ARRAY column reference"
                )
            c = page.column(a.name)
            if c.array_pool is None:
                raise NotImplementedError(
                    f"UNNEST: {a.name} carries no array pool"
                )
            handles = np.asarray(c.data)[sel]
            valid = (
                None if c.valid is None else np.asarray(c.valid)[sel]
            )
            lens = c.array_pool.lengths()[handles]
            if valid is not None:
                lens = np.where(valid, lens, 0)
            args.append((c.array_pool, handles, lens))
        row_len = args[0][2]
        for _, _, ln in args[1:]:
            row_len = np.maximum(row_len, ln)
        total = int(row_len.sum())
        if total == 0:
            # empty expansion (no live rows / all arrays empty-or-NULL)
            cap0 = pad_capacity(1)
            names0 = list(page.names) + list(node.element_symbols)
            cols0 = [
                Column(c.type, c.data[:cap0],
                       None if c.valid is None else c.valid[:cap0],
                       c.dictionary, c.hash_pool, c.array_pool)
                for c in page.columns
            ] + [
                Column.from_numpy(
                    node.outputs[s],
                    np.zeros(
                        0,
                        dtype=object if isinstance(
                            node.outputs[s], T.VarcharType
                        ) else node.outputs[s].np_dtype,
                    ),
                    capacity=cap0,
                )
                for s in node.element_symbols
            ]
            return Page(
                names0, cols0,
                jnp.zeros((cap0,), dtype=jnp.bool_),
                known_rows=0, packed=True,
            )
        out_cap = shapes.bucket(max(total, 1), site="unnest")
        # source-row index per output row + within-array position
        src = np.repeat(sel, row_len)
        starts = np.concatenate([[0], np.cumsum(row_len)[:-1]])
        within = np.arange(total, dtype=np.int64) - np.repeat(starts, row_len)
        # gather the source columns device-side by the uploaded index
        idx_pad = np.zeros(out_cap, dtype=np.int32)
        idx_pad[:total] = src
        idx_dev = jnp.asarray(idx_pad)
        names, cols = [], []
        for n2, c in zip(page.names, page.columns):
            cols.append(Column(
                c.type, c.data[idx_dev],
                None if c.valid is None else c.valid[idx_dev],
                c.dictionary, c.hash_pool, c.array_pool,
            ))
            names.append(n2)
        # element columns from pool slices (host gather — the values
        # buffer is host-resident by design)
        for sym, (pool, handles, lens) in zip(node.element_symbols, args):
            ln_rep = np.repeat(lens, row_len)
            offs = np.repeat(pool.offsets[:-1][handles], row_len)
            ok = within < ln_rep
            at = np.where(ok, offs + within, 0)
            if len(pool.values):
                vals = pool.values[np.clip(at, 0, len(pool.values) - 1)]
            else:
                vals = np.zeros(
                    total,
                    dtype=object if isinstance(
                        node.outputs[sym], T.VarcharType
                    ) else node.outputs[sym].np_dtype,
                )
            if isinstance(node.outputs[sym], T.VarcharType):
                vals = np.where(ok, vals, "")
            cols.append(Column.from_numpy(
                node.outputs[sym], vals, valid=ok, capacity=out_cap,
            ))
            names.append(sym)
        out_mask = np.zeros(out_cap, dtype=np.bool_)
        out_mask[:total] = True
        return Page(
            names, cols, jnp.asarray(out_mask),
            known_rows=total, packed=True,
        )

    def _Window(self, node: P.Window) -> Page:
        from trino_tpu.exec.window import build_window_program

        page = self.execute(node.source)
        key = (
            "window", tuple(node.partition_by),
            tuple(
                (k.symbol, k.ascending, k.nulls_first)
                for k in node.order_keys
            ),
            tuple(
                (s, c.name, repr(c.args), repr(c.frame))
                for s, c in node.functions.items()
            ),
            self._layout_sig(page),
        )
        hit = self._jit_cache.get(key)
        with _dispatching("window", hit is None):
            if hit is None:
                types = {n: c.type for n, c in zip(page.names, page.columns)}
                dicts = {
                    n: c.dictionary for n, c in zip(page.names, page.columns)
                }
                fn, out_meta = build_window_program(
                    node, types, dicts, page.capacity
                )
                hit = (_named_jit(fn, "window"), out_meta)
                self._jit_cache[key] = hit
            fn, out_meta = hit
            env2 = fn(self._env(page), page.mask)
        names = list(page.names)
        cols = list(page.columns)
        for sym, t, d in out_meta:
            names.append(sym)
            cols.append(Column(t, *env2[sym], d))
        return Page(
            names, cols, page.mask,
            known_rows=page.known_rows, packed=page.packed,
        )

    def _Union(self, node: P.Union) -> Page:
        from trino_tpu.page import StringDictionary, _remap

        pages = [self.execute(s) for s in node.all_sources]
        # unify dictionaries per output column across branches: one
        # merged sorted dictionary, each branch remapped by gather
        for sym, src_syms in node.symbol_map.items():
            cols = [p.column(s) for p, s in zip(pages, src_syms)]
            if any(c.hash_pool is not None for c in cols):
                raise NotImplementedError(
                    "hash-coded varchar columns cannot merge across "
                    "UNION branches (no shared dictionary)"
                )
            if any(c.dictionary is not None for c in cols):
                # a branch may carry a dictionary-less varchar column
                # (typed NULL literals, e.g. a global grouping-set
                # branch's NULLed keys): treat it as an empty dictionary
                empty = np.asarray([], dtype=object)
                merged = StringDictionary(np.unique(np.concatenate(
                    [
                        c.dictionary.values if c.dictionary is not None
                        else empty
                        for c in cols
                    ]
                )))
                for p, s, c in zip(pages, src_syms, cols):
                    vals = (
                        c.dictionary.values if c.dictionary is not None
                        else empty
                    )
                    remap = np.searchsorted(
                        merged.values, vals
                    ).astype(np.int32)
                    p.columns[p.names.index(s)] = _remap(c, remap, merged)
        names, cols = [], []
        for sym, src_syms in node.symbol_map.items():
            parts = [
                (p.column(s).data, p.column(s).valid)
                for p, s in zip(pages, src_syms)
            ]
            out_t = node.outputs[sym]
            if isinstance(out_t, T.DecimalType) and out_t.is_long:
                # a branch may carry the column 1-D (typed NULL
                # literals / short-encoded values); widen to limbs so
                # sections concatenate shape-consistently
                from trino_tpu.exec.aggregates import _limb_encode

                parts = [
                    (
                        d if jnp.ndim(d) == 2
                        else _limb_encode(d.astype(jnp.int64)),
                        v,
                    )
                    for d, v in parts
                ]
            data, valid = _concat_sections(parts)
            ref = pages[0].column(src_syms[0])
            names.append(sym)
            cols.append(Column(node.outputs[sym], data, valid, ref.dictionary))
        mask = jnp.concatenate([p.mask for p in pages])
        out = Page(names, cols, mask)
        if all(p.known_rows is not None for p in pages):
            out.known_rows = sum(p.known_rows for p in pages)
        return out

    # ---- semi join -------------------------------------------------------

    def _SemiJoin(self, node: P.SemiJoin) -> Page:
        kind = self._take_negated_match(node)
        budget = self.hbm_budget()
        if not budget:
            self._prefetch_join_chains(node)
        if budget:
            from trino_tpu.exec import spill

            src_chain, src_scan = self._streamable(node.source)
            if (
                src_scan is not None
                and spill.est_output_bytes(self, node.source) > budget // 4
                and spill.est_output_bytes(self, node.filter_source)
                <= budget // 4
            ):
                filt = self._compact(self.execute(node.filter_source))
                return spill.streamed_semi_join(
                    self, node, src_chain, src_scan, filt
                )
        source = self.execute(node.source)
        filt = self._compact(self.execute(node.filter_source))
        return self._semi_join_pages(node, source, filt, kind)

    def _semi_join_pages(
        self, node: P.SemiJoin, source: Page, filt: Page, kind: str = "semi"
    ) -> Page:
        self._unify_join_dicts(source, filt, node.keys)
        pv = bv = None
        for lsym, rsym in node.keys:
            pv = _and_mask(pv, source.column(lsym).valid)
            bv = _and_mask(bv, filt.column(rsym).valid)
        needs_expand = len(node.keys) > 1 or node.filter is not None
        if needs_expand:
            order, lo, cnt, total = self._join_count(
                node.keys, source, filt, node.key_ranges, kind
            )
            out_cap = shapes.bucket(max(total, 1), site="semi-join")
            key = (
                "semiB", tuple(node.keys), repr(node.filter), out_cap,
                self._layout_sig(source), self._layout_sig(filt),
            )
            fn = self._jit_cache.get(key)
            with _dispatching("semi_join", fn is None):
                if fn is None:
                    fn = self._build_semi_expand(
                        node, source, filt, out_cap
                    )
                    self._jit_cache[key] = fn
                matched = fn(
                    self._env(source), self._env(filt), order, lo, cnt
                )
        else:
            crit = list(node.keys)
            key_lo, key_bits = self._join_key_width(
                node.key_ranges, crit, source, filt
            )
            key = (
                "semiA", tuple(node.keys), key_lo, key_bits,
                self._layout_sig(source), self._layout_sig(filt),
            )
            fn = self._jit_cache.get(key)
            with _dispatching("semi_join", fn is None) as dispatch:
                dispatch.note_join(filt.capacity, key_bits, kind)
                if fn is None:
                    kinds = self._join_key_kinds(source, filt, crit)

                    def fa(penv, pmask, benv, bmask):
                        pk, bk, pv2, bv2, _, _ = self._traced_join_keys(
                            penv, benv, crit, kinds, key_lo
                        )
                        probe_live = (
                            pmask if pv2 is None else (pmask & pv2)
                        )
                        build_live = (
                            bmask if bv2 is None else (bmask & bv2)
                        )
                        _, _, cnt = K.join_ranges(
                            bk, build_live, pk, probe_live,
                            key_bits=key_bits,
                        )
                        return cnt > 0

                    fn = _named_jit(fa, "semi_join")
                    self._jit_cache[key] = fn
                matched = fn(
                    self._env(source), source.mask,
                    self._env(filt), filt.mask,
                )
        valid = None
        if node.null_aware and filt.num_rows() == 0:
            # x IN (empty) is FALSE — even for NULL x (and NOT IN TRUE);
            # the 3VL valid mask must not apply over an empty build side.
            pass
        elif node.null_aware:
            # IN 3VL: NULL probe key with a nonempty (per-probe) set,
            # or no match while the set has NULLs -> NULL (reference
            # SemiJoinNode semantics). EXISTS is 2-valued.
            build_null_for = self._in_build_nulls(node, source, filt, bv)
            if pv is not None or build_null_for is not None:
                valid = jnp.ones_like(matched)
                if build_null_for is not None:
                    valid = valid & (matched | ~build_null_for)
                if pv is not None:
                    if node.filter is None:
                        # the set is the whole (nonempty) build side
                        valid = valid & pv
                    else:
                        # NULL probe key is FALSE, not NULL, when its
                        # correlated set filters down to empty
                        nonempty = self._correlated_nonempty(
                            node, source, filt, pv
                        )
                        valid = valid & (pv | ~nonempty)
        names = list(source.names) + [node.match_symbol]
        cols = list(source.columns) + [
            Column(T.BOOLEAN, matched, valid, None)
        ]
        return Page(
            names, cols, source.mask,
            known_rows=source.known_rows, packed=source.packed,
        )

    def _in_build_nulls(self, node: P.SemiJoin, source: Page, filt: Page, bv):
        """Per-probe 'the build side contributed a NULL key' vector for
        IN 3VL, or None when no NULL keys exist.

        With a correlated residual filter, only NULL-key build rows
        that pass the filter against that probe row count (the review
        case: x NOT IN (select y from t where t.z <> outer.w))."""
        if bv is None:
            return None
        null_rows = np.nonzero(np.asarray(filt.mask & ~bv))[0]
        if len(null_rows) == 0:
            return None
        if node.filter is None:
            return jnp.ones((source.capacity,), dtype=jnp.bool_)
        any_null = jnp.zeros((source.capacity,), dtype=jnp.bool_)
        probe_idx = jnp.arange(source.capacity, dtype=jnp.int32)
        for r in null_rows.tolist():
            build_idx = jnp.full((source.capacity,), r, dtype=jnp.int32)
            pair = self._gather_pair_page(
                source, filt, probe_idx, build_idx, source.mask
            )
            fd, fv, _ = self._eval(pair, node.filter)
            passes = fd if fv is None else (fd & fv)
            any_null = any_null | passes
        return any_null

    def _correlated_nonempty(self, node: P.SemiJoin, source: Page, filt: Page, pv):
        """Per-probe 'some live build row passes the residual filter'
        vector — the per-probe set of a correlated IN is empty when no
        build row passes against that probe row. Only NULL-key probe
        rows need it, so loop over those (usually few), evaluating the
        filter against the whole build page per row."""
        nonempty = np.zeros((source.capacity,), dtype=np.bool_)
        need = np.nonzero(np.asarray(source.mask & ~pv))[0]
        if len(need) == 0:
            return jnp.asarray(nonempty)
        build_idx = jnp.arange(filt.capacity, dtype=jnp.int32)
        for i in need.tolist():
            probe_idx = jnp.full((filt.capacity,), i, dtype=jnp.int32)
            pair = self._gather_pair_page(
                source, filt, probe_idx, build_idx, filt.mask
            )
            fd, fv, _ = self._eval(pair, node.filter)
            passes = fd if fv is None else (fd & fv)
            nonempty[i] = bool(np.asarray(jnp.any(passes & filt.mask)))
        return jnp.asarray(nonempty)

    @staticmethod
    def _gather_pair_page(probe: Page, build: Page, probe_idx, build_idx, live) -> Page:
        names, cols = [], []
        for page, idx in ((probe, probe_idx), (build, build_idx)):
            for n, c in zip(page.names, page.columns):
                names.append(n)
                cols.append(
                    Column(
                        c.type,
                        c.data[idx],
                        None if c.valid is None else c.valid[idx],
                        c.dictionary,
                    )
                )
        return Page(names, cols, live)


def _declared_order(node: P.TableScan, connector) -> str | None:
    """The symbol a whole-table scan assigns to the column the
    connector declares its rows ascend on (``Connector.sorted_by``);
    not a hash-coded varchar, whose lanes are not the value's order."""
    sorted_col = connector.sorted_by(node.schema, node.table)
    hashed = node.hash_varchar or ()
    return next(
        (
            s for s, c in node.assignments.items()
            if c == sorted_col and s not in hashed
        ),
        None,
    )


def _unordered_key(caps_key: tuple) -> tuple:
    """Where the executor remembers, beside a chain shape's learned
    capacities, that its input broke the connector's declared order."""
    return ("unordered",) + caps_key[1:]


def _splittable(agg: P.Aggregate) -> bool:
    """Probe partial/final decomposability BEFORE running chunks, so a
    non-splittable aggregate never discards computed partials."""
    from trino_tpu.plan.distribute import _split_aggregate

    try:
        _split_aggregate(agg)
        return True
    except NotImplementedError:
        return False


def _page_dev_bytes(page: Page) -> int:
    """Actual device bytes of a page's arrays (mask + data + valids)."""
    total = page.mask.shape[0]  # bool
    for c in page.columns:
        n = 1
        for d in c.data.shape:
            n *= int(d)
        total += n * c.data.dtype.itemsize
        if c.valid is not None:
            total += c.valid.shape[0]
    return total


def _rename_out(out_layout, env: dict, out_map: dict):
    """Translate a canonical chain program's output layout + env back
    to the caller's original symbol names (see shapes.canonicalize_chain).
    Builds fresh structures — the cached layout is shared across calls."""
    m = out_map
    layout = stage.ChainLayout(
        names=[m[n] for n in out_layout.names],
        types={m[n]: out_layout.types[n] for n in out_layout.names},
        dicts={m[n]: out_layout.dicts.get(n) for n in out_layout.names},
        capacity=out_layout.capacity,
        pools={m[n]: p for n, p in out_layout.pools.items() if n in m},
        arrays={m[n]: a for n, a in out_layout.arrays.items() if n in m},
        ordered_on=m.get(out_layout.ordered_on),
    )
    env2 = {m[n]: env[n] for n in out_layout.names}
    return layout, env2


def _slice_page(page: Page, lo: int, hi: int) -> Page:
    """Row-range view of a page (device slices are cheap)."""
    cols = [
        Column(
            c.type, c.data[lo:hi],
            None if c.valid is None else c.valid[lo:hi],
            c.dictionary,
            c.hash_pool,
            c.array_pool,
        )
        for c in page.columns
    ]
    return Page(list(page.names), cols, page.mask[lo:hi])


def _concat_pages(pages: list[Page]) -> Page:
    """Concatenate same-layout pages (chunk partials share column
    types and dictionaries by construction)."""
    if len(pages) == 1:
        return pages[0]
    first = pages[0]
    cols = []
    for i, c in enumerate(first.columns):
        data = jnp.concatenate([p.columns[i].data for p in pages])
        if any(p.columns[i].valid is not None for p in pages):
            valid = jnp.concatenate([
                (
                    # [:1]: valids are per-ROW even for multi-lane data
                    # (two-limb decimals, sketch states)
                    jnp.ones(p.columns[i].data.shape[:1], dtype=jnp.bool_)
                    if p.columns[i].valid is None else p.columns[i].valid
                )
                for p in pages
            ])
        else:
            valid = None
        cols.append(Column(c.type, data, valid, c.dictionary, c.hash_pool, c.array_pool))
    mask = jnp.concatenate([p.mask for p in pages])
    return Page(list(first.names), cols, mask)


def _pair_layout(a: Page, b: Page) -> ColumnLayout:
    """Expression layout over the concatenated columns of two pages
    (for residual join filters evaluated on matched pairs)."""
    return ColumnLayout(
        types={
            **{n: c.type for n, c in zip(a.names, a.columns)},
            **{n: c.type for n, c in zip(b.names, b.columns)},
        },
        dictionaries={
            **{n: c.dictionary for n, c in zip(a.names, a.columns)},
            **{n: c.dictionary for n, c in zip(b.names, b.columns)},
        },
    )


def _gather_pair_env(penv, benv, probe_names, syms, probe_idx, build_idx, base=None):
    """Expanded-pair environment for the given symbols (traced)."""
    fenv = dict(base or {})
    for sym in syms:
        if sym not in fenv:
            from_probe = sym in probe_names
            d, v = (penv if from_probe else benv)[sym]
            idx = probe_idx if from_probe else build_idx
            fenv[sym] = K.rows_at(d, v, idx)
    return fenv


def _concat_sections(parts):
    """Concatenate (data, valid|None) sections; None = all-valid."""
    if len(parts) == 1:
        return parts[0]
    data = jnp.concatenate([d for d, _ in parts])
    if all(v is None for _, v in parts):
        return data, None
    valids = [
        jnp.ones(d.shape[:1], dtype=jnp.bool_) if v is None else v
        for d, v in parts
    ]
    return data, jnp.concatenate(valids)


def _expr_symbols(e: RowExpression) -> set[str]:
    """Free input symbols of an expression tree."""
    out: set[str] = set()
    stack = [e]
    while stack:
        x = stack.pop()
        if isinstance(x, InputRef):
            out.add(x.name)
        elif isinstance(x, Call):
            stack.extend(x.args)
        elif isinstance(x, Cast):
            stack.append(x.arg)
    return out


def _shifted(data, lo: int):
    """An integer join key moved to its range's origin (in int64: the
    range of a narrower column may not fit its own dtype)."""
    if lo == 0:
        return data
    return data.astype(jnp.int64) - jnp.int64(lo)


def _and_mask(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a & b
