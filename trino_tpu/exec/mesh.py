"""Distributed plan execution over a device mesh.

The analog of the reference's worker tier — SqlTaskExecution running
fragment pipelines plus the shuffle subsystem
(MAIN/execution/SqlTaskExecution.java:83, PartitionedOutputOperator ->
HTTP exchange -> ExchangeOperator, SURVEY.md §3.4) — rebuilt SPMD:

- a ``ShardedPage`` is the distributed Page: every column is ONE jax
  array sharded over the mesh axis (global shape
  [n_shards * shard_capacity]); shard i owns its slice. No serde, no
  buffers — device arrays stay device arrays.
- fusable operator chains compile to one ``shard_map``-ped XLA program
  (each shard runs the same fused pipeline on its rows);
- the hash ``Exchange`` is one ``lax.all_to_all`` on ICI
  (parallel.exchange.partition_exchange) inside the same SPMD program
  style — the whole shuffle is a collective, not a protocol; over a
  page already ranged and ordered on the exchange's one key
  (``ShardedPage.ordered_on``) it is satisfied where the rows lie
  (``exchange_in_place``: AddExchanges.visitAggregation places no
  exchange under an aggregation whose child is partitioned on the
  grouping keys; here the plan keeps its node and the executor finds
  it has nothing to move but the runs a shard boundary cuts);
- joins co-partition or broadcast their build side and run shard-local
  sort-probe joins; data-dependent output capacities are resolved with
  one host sync (count phase, then expand phase) — mirroring the
  reference's build-side barrier;
- ``Exchange(single)`` gathers a ShardedPage into an ordinary Page;
  everything above it (final TopN, output formatting) runs on the
  inherited single-device executor — the coordinator's final stage.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from trino_tpu import program_catalog, telemetry, types as T
from trino_tpu.exec import kernels as K
from trino_tpu.exec import shapes as shape_policy
from trino_tpu.exec import stage
from trino_tpu.exec.failure import FailureInjector, InjectedFailure
from trino_tpu.exec.local import (
    LocalExecutor,
    _chain_program_name,
    _declared_order,
    _dispatching,
    _named_jit,
    _rename_out,
    _shifted,
    _unordered_key,
)
from trino_tpu.expr.compiler import compile_expr, ColumnLayout
from trino_tpu.metadata import Metadata, Session
from trino_tpu.page import Column, Page, pad_capacity, unify_dictionaries
from trino_tpu.parallel.core import WORKER_AXIS, make_mesh
from trino_tpu.parallel.exchange import partition_exchange, seam_exchange
from trino_tpu.plan import nodes as P

__all__ = ["MeshExecutor", "ShardedPage", "SkewOverflow"]


class SkewOverflow(RuntimeError):
    """An exchange destination overflowed the per-shard capacity even
    at the maximum bucket size — hash partitioning alone cannot place
    this data (a hot key owns more rows than one shard holds). Join
    callers recover via the skew-split path; other callers surface it."""


@dataclass
class ShardedPage:
    """Columnar batch sharded along the mesh's worker axis.

    Column data has global shape [n_shards * shard_capacity] with a
    NamedSharding over the axis. As a rule it is a bag of rows, like
    the reference's distributed Pages: where a row lies says nothing.

    ``ordered_on`` names the one exception, the twin of
    ``Page.ordered_on`` across shards: inside every shard the live
    rows are a prefix and ascend on that column (by its normalized key
    bits, the order ``kernels.run_group`` checks), and shard i's rows
    precede shard i+1's in that order, so a key's rows lie on one
    shard or at the seam of adjacent ones. *Declared* by the resident
    whole-table scan alone (``_scan_dist``: ``Connector.sorted_by``
    over rows range-sharded in scan order). *Kept* by a chain whose
    steps keep it (``stage.build_chain``: a Project that passes the
    column through, an Aggregate grouped on it alone) and by
    ``exchange_in_place``; every other producer — ``scatter``, a split
    scan, a remote source, a join, a concatenation, a GroupId, the
    general exchange — builds its page without it. *Verified* where it
    is used: the streamed group-by checks each shard's order on the
    device (a chain reruns by sort and drops the property),
    ``exchange_in_place`` checks the order inside and across shards
    (and falls back to the hash exchange)."""

    names: list[str]
    columns: list[Column]
    mask: jnp.ndarray
    n_shards: int
    ordered_on: str | None = None

    @property
    def shard_capacity(self) -> int:
        return self.mask.shape[0] // self.n_shards

    def column(self, name: str) -> Column:
        return self.columns[self.names.index(name)]


def _exchange_key_pairs(keys):
    """(bits, valid) pairs for exchange-key hashing from ``(data,
    valid, hashed)`` triples: hash-coded varchar contributes its hash
    lane only (the id lane is row identity and would split equal
    strings across destinations); two-limb decimals contribute both
    limbs."""
    pairs = []
    for data, valid, hashed in keys:
        if hashed:
            pairs.append((data[:, 0], valid))
            continue
        parts = K.limb_parts(data)
        pairs.extend(
            (p, valid if i == 0 else None)
            for i, p in enumerate(parts)
        )
    return pairs


def _page_leaves(page) -> tuple[list, list[tuple[str, bool]]]:
    """Flatten a (Sharded)Page into [data, valid?...] leaves + mask."""
    leaves, meta = [], []
    for n, c in zip(page.names, page.columns):
        leaves.append(c.data)
        if c.valid is not None:
            leaves.append(c.valid)
        meta.append((n, c.valid is not None))
    leaves.append(page.mask)
    return leaves, meta


def _env_from_leaves(leaves, meta):
    env, i = {}, 0
    for name, has_valid in meta:
        data = leaves[i]
        i += 1
        valid = None
        if has_valid:
            valid = leaves[i]
            i += 1
        env[name] = (data, valid)
    return env, leaves[i]


def _columns_from_leaves(leaves, meta, like: list[Column]) -> list[Column]:
    """``_page_leaves`` undone for a page's columns: the program's data
    and validity lanes (in ``meta``'s order) as Columns that keep the
    type, dictionary and pool of ``like``'s."""
    cols, i = [], 0
    for (_name, has_valid), c in zip(meta, like):
        data = leaves[i]
        i += 1
        valid = None
        if has_valid:
            valid = leaves[i]
            i += 1
        cols.append(Column(c.type, data, valid, c.dictionary, c.hash_pool))
    return cols


def _make_prelude(criteria, p_meta, b_meta, n_p, verify, kinds=None, lo=0):
    """Shared shard-local join-key builder for equi and semi joins:
    splits the flat leaves back into probe/build envs and produces
    normalized key bits, combined keys, and 3VL-aware live masks.
    ``kinds[i] == 'hash'`` marks hash-coded varchar criteria (key =
    hash lane only). ``lo`` (``LocalExecutor._join_key_width``): the
    origin the one integer key of a join whose range the plan proves
    is shifted to, as ``_traced_join_keys`` shifts it."""

    def prelude(ls):
        p_env, p_mask = _env_from_leaves(list(ls[:n_p]), p_meta)
        b_env, b_mask = _env_from_leaves(list(ls[n_p:]), b_meta)
        pv = bv = None
        p_bits, b_bits = [], []
        for i, (lsym, rsym) in enumerate(criteria):
            pd, pvx = p_env[lsym]
            bd, bvx = b_env[rsym]
            if pvx is not None:
                pv = pvx if pv is None else (pv & pvx)
            if bvx is not None:
                bv = bvx if bv is None else (bv & bvx)
            if kinds is not None and kinds[i] == "hash":
                p_bits.append(K.normalize_key(pd[:, 0], None)[0])
                b_bits.append(K.normalize_key(bd[:, 0], None)[0])
                continue
            # two-limb decimal keys expand into hi/lo parts
            for part in K.limb_parts(pd):
                p_bits.append(K.normalize_key(_shifted(part, lo), None)[0])
            for part in K.limb_parts(bd):
                b_bits.append(K.normalize_key(_shifted(part, lo), None)[0])
        if verify or len(p_bits) > len(criteria):
            pk = K.hash_columns([(b, None) for b in p_bits])
            bk = K.hash_columns([(b, None) for b in b_bits])
        else:
            pk, bk = p_bits[0], b_bits[0]
        probe_live = p_mask if pv is None else (p_mask & pv)
        build_live = b_mask if bv is None else (b_mask & bv)
        return (
            p_env, p_mask, b_env, b_mask,
            pk, bk, probe_live, build_live, p_bits, b_bits,
        )

    return prelude


class MeshExecutor(LocalExecutor):
    """Executes distribution-planned trees (plan.distribute) over a
    jax.sharding.Mesh; single-prop regions fall through to the
    inherited local executor."""

    def __init__(
        self,
        metadata: Metadata,
        session: Session,
        mesh: Mesh | None = None,
        axis: str = WORKER_AXIS,
    ):
        super().__init__(metadata, session)
        self.mesh = mesh if mesh is not None else make_mesh()
        self.axis = axis
        self.n_shards = int(self.mesh.shape[axis])
        self._row_sharding = NamedSharding(self.mesh, PS(axis))
        self._dist_scan_cache: dict = {}
        self._mesh_jit_cache: dict = telemetry.CountingCache("mesh")
        #: test hook: arm per-stage failures; stage programs retry
        #: (FailureInjector analog, MAIN/execution/FailureInjector.java:39)
        self.failure_injector = FailureInjector()
        #: count of joins that took the skew-split path (tests/metrics)
        self.skew_joins = 0
        #: count of exchange bucket-capacity escalations (tests assert
        #: skew-proof plans never escalate)
        self.exchange_escalations = 0
        #: per-query exchange telemetry (EXPLAIN ANALYZE + tests):
        #: all_to_all count and device bytes moved through them
        self.exchange_stats = {"exchanges": 0, "bytes": 0}

    def _shard_jit(self, fn, name: str, in_specs, out_specs, entry=None):
        """One SPMD program over the mesh, named as the local executor
        names its own (``_named_jit``): the device trace and the program
        catalog read ``jit_mesh_<name>``. ``entry`` is the scope a
        chain's way into and out of the shards is charged to (its first
        operator's; a program that is one operator lies under that
        operator's scope whole)."""
        return _named_jit(
            jax.shard_map(
                fn, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False,
            ),
            "mesh_" + name, scope=entry,
        )

    def _run(
        self, prog, miss: bool, *args, tag: str | None = None,
        join_build: int | None = None, key_bits: int = 64,
        join_kind: str = "inner", distinct_of=None, **note
    ):
        """``prog(*args)`` under a ``dispatch`` span that carries the
        program's name (with a ``build_trace`` child on a jit-cache
        miss, as the local executor's), ``note``'s attributes and,
        where the program holds a ``kernels.join_ranges``, the search
        it was built with for a build of ``join_build`` rows and the
        width its keys are ranked at (``key_bits``) and what it joins
        as (``join_kind``), and, where it is the chain ``distinct_of``,
        that chain's DISTINCT aggregate calls, through ``_attempt``
        where the site is a retry unit."""
        with _dispatching(prog.__name__, miss) as dispatch:
            if note:
                dispatch.note(**note)
            if join_build is not None:
                dispatch.note_join(join_build, key_bits, join_kind)
            if distinct_of is not None:
                dispatch.note_distinct(distinct_of)
            if tag is None:
                return prog(*args)
            return self._attempt(tag, lambda: prog(*args))

    def _attempt(self, tag: str, call):
        """Run one stage-shard program with injected-failure retry.

        The retry unit of the fault-tolerant scheduler
        (EventDrivenFaultTolerantQueryScheduler analog): stage inputs
        are retained device arrays, so a failed invocation simply
        re-runs against them — the spooled-stage-output durability of
        the reference comes free from XLA buffer lifetimes."""
        from trino_tpu import fault

        attempt = 0
        while True:
            try:
                self.failure_injector.check(tag, attempt)
                # the process-global chaos injector (trino_tpu.fault)
                # addresses mesh stages through the same task-exec
                # site, so multi-site chaos runs compose with the
                # executor-local injector
                fault.check("task-exec", tag, attempt)
                return call()
            except fault.InjectedFault:
                attempt += 1
                if attempt >= self.failure_injector.max_attempts:
                    raise

    # ---- boundaries ------------------------------------------------------

    def _Exchange(self, node: P.Exchange) -> Page:
        if node.partitioning != "single":
            raise AssertionError(
                "non-single exchange reached the local executor"
            )
        return self.gather(self.execute_dist(node.source))

    def execute_dist(self, node: P.PlanNode) -> ShardedPage:
        self._check_cancel()
        if isinstance(node, stage.FUSABLE):
            chain: list[P.PlanNode] = []
            cur = node
            while isinstance(cur, stage.FUSABLE):
                chain.append(cur)
                cur = cur.sources[0]
            self._note_negated_match(chain[-1], cur)
            base = self.execute_dist(cur)
            return self._run_chain_sharded(list(reversed(chain)), base)
        if isinstance(node, P.RemoteSource):
            # fleet x mesh seam: a spooled stage input scatters over
            # THIS worker's device mesh; when the producing stage hash-
            # partitioned on keys, re-exchange locally so every key
            # owns one shard (FINAL aggregation / co-partitioned
            # consumers assume key-disjoint shards) — the DCN partition
            # re-partitions over ICI within the worker (SURVEY §5.8)
            page = self._RemoteSource(node)
            sp = self.scatter(page)
            keys = (getattr(self, "remote_hash_keys", None) or {}).get(
                node.source_id
            )
            if keys and all(k in sp.names for k in keys):
                sp = self.hash_exchange(sp, keys)
            return sp
        if isinstance(node, P.TableScan):
            if node.split is not None:
                # a fleet split-bound scan covers [start, start+count)
                # only: run the local split scan, then shard it (the
                # whole-table dist cache would double-count)
                return self.scatter(self._TableScan(node))
            return self._scan_dist(node)
        if isinstance(node, P.Exchange):
            if node.partitioning == "hash":
                sp = self.execute_dist(node.source)
                if list(node.hash_symbols) == [sp.ordered_on]:
                    # ranged and ordered on the exchange's key already
                    return self.exchange_in_place(sp)
                return self.hash_exchange(sp, node.hash_symbols)
            if node.partitioning == "range":
                sp = self.execute_dist(node.source)
                return self.range_exchange(sp, node.sort_keys)
            raise AssertionError(
                f"exchange {node.partitioning} cannot produce a sharded page"
            )
        if isinstance(node, P.Join):
            return self._dist_join(node)
        if isinstance(node, P.SemiJoin):
            return self._dist_semi(node)
        if isinstance(node, P.GroupId):
            return self._dist_groupid(node)
        raise NotImplementedError(
            f"no distributed executor for {type(node).__name__}"
        )

    # ---- scan / gather / scatter ----------------------------------------

    def invalidate_scan(self, catalog: str, schema: str, table: str):
        super().invalidate_scan(catalog, schema, table)
        self._dist_scan_cache.pop((catalog, schema, table), None)

    def _shard_layout(self, n: int) -> tuple[int, int]:
        """(rows per shard, padded per-shard capacity) for n rows."""
        per = -(-max(n, 1) // self.n_shards)  # ceil
        return per, pad_capacity(per)

    def _shard_split(self, host: np.ndarray, n: int, per: int, cap: int):
        """Lay n host rows contiguously into the [n_shards * cap]
        sharded layout and put it on the mesh."""
        out = np.zeros(
            (self.n_shards * cap,) + host.shape[1:], dtype=host.dtype
        )
        for s in range(self.n_shards):
            take = min(max(n - s * per, 0), per)
            out[s * cap: s * cap + take] = host[s * per: s * per + take]
        return jax.device_put(out, self._row_sharding)

    def _scan_dist(self, node: P.TableScan) -> ShardedPage:
        key = (node.catalog, node.schema, node.table)
        if not self.metadata.connector(node.catalog).cacheable:
            cache = {}  # live views re-scan per query
        else:
            cache = self._dist_scan_cache.setdefault(key, {})
        hashed_syms = set(node.hash_varchar or [])

        def ckey(sym, cname):
            return f"#hash:{cname}" if sym in hashed_syms else cname

        missing = [
            (sym, c) for sym, c in node.assignments.items()
            if ckey(sym, c) not in cache
        ]
        if missing or "" not in cache:
            with telemetry.child_span(
                "mesh-scan-upload", table=node.table,
                columns=len(missing),
            ):
                connector = self.metadata.connector(node.catalog)
                cols = connector.scan(
                    node.schema, node.table, [c for _, c in missing]
                )
                if missing:
                    first = cols[missing[0][1]]
                    n = len(first[0] if isinstance(first, tuple) else first)
                else:
                    n = connector.row_count(node.schema, node.table)
                per, cap = self._shard_layout(n)
                if "" not in cache:
                    cache[""] = self._shard_split(
                        np.ones(n, dtype=np.bool_), n, per, cap
                    )
                for sym, cname in missing:
                    v = cols[cname]
                    valid = None
                    if isinstance(v, tuple):
                        v, valid = v
                    if sym in hashed_syms:
                        from trino_tpu.exec.local import _hash_varchar_column

                        # global pool + global row ids: the id lane stays
                        # meaningful on every shard (pools are host-side)
                        col = _hash_varchar_column(
                            node.outputs[sym], np.asarray(v, dtype=object),
                            valid, max(n, 1),
                        )
                    else:
                        col = Column.from_numpy(
                            node.outputs[sym], v, valid=valid,
                            capacity=max(n, 1),
                        )
                    cache[ckey(sym, cname)] = Column(
                        col.type,
                        self._shard_split(
                            np.asarray(col.data)[:n], n, per, cap
                        ),
                        None if col.valid is None else self._shard_split(
                            np.asarray(col.valid)[:n], n, per, cap
                        ),
                        col.dictionary,
                        col.hash_pool,
                    )
        names = list(node.assignments)
        columns = [
            cache[ckey(s, c)] for s, c in node.assignments.items()
        ]
        # the whole table in the connector's order, cut into consecutive
        # ranges: the one sharded page that carries the declared order
        return ShardedPage(
            names, columns, cache[""], self.n_shards,
            ordered_on=_declared_order(
                node, self.metadata.connector(node.catalog)
            ),
        )

    def gather(self, sp: ShardedPage) -> Page:
        """ShardedPage -> compacted single-device Page (the reference's
        root-stage output buffer drain). The wait for the programs that
        make the page is a ``host_sync``; the transfers and the host's
        compaction are the ``mesh-gather`` span."""
        with telemetry.child_span("host_sync", site="mesh_gather"):
            jax.block_until_ready(_page_leaves(sp)[0])
        with telemetry.child_span("mesh-gather") as span:
            page = self._gather(sp)
            if span is not None:
                span.attrs["rows"] = page.known_rows
            return page

    def _gather(self, sp: ShardedPage) -> Page:
        mask = np.asarray(sp.mask)
        idx = np.nonzero(mask)[0]
        cap = pad_capacity(len(idx))
        cols = []
        for c in sp.columns:
            src = np.asarray(c.data)
            data = np.zeros((cap,) + src.shape[1:], dtype=src.dtype)
            data[: len(idx)] = src[idx]
            valid = None
            if c.valid is not None:
                v = np.zeros(cap, dtype=np.bool_)
                v[: len(idx)] = np.asarray(c.valid)[idx]
                valid = jnp.asarray(v)
            cols.append(Column(c.type, jnp.asarray(data), valid, c.dictionary, c.hash_pool))
        out_mask = np.zeros(cap, dtype=np.bool_)
        out_mask[: len(idx)] = True
        return Page(
            list(sp.names), cols, jnp.asarray(out_mask),
            known_rows=len(idx), packed=True,
        )

    def scatter(self, page: Page) -> ShardedPage:
        """Split a local Page's live rows contiguously over the mesh."""
        with telemetry.child_span("host_sync", site="mesh_scatter"):
            jax.block_until_ready(_page_leaves(page)[0])
        with telemetry.child_span("mesh-scatter"):
            return self._scatter(page)

    def _scatter(self, page: Page) -> ShardedPage:
        idx = np.nonzero(np.asarray(page.mask))[0]
        n = len(idx)
        per, cap = self._shard_layout(n)
        cols = []
        for c in page.columns:
            valid = None
            if c.valid is not None:
                valid = self._shard_split(
                    np.asarray(c.valid)[idx], n, per, cap
                )
            cols.append(
                Column(
                    c.type,
                    self._shard_split(np.asarray(c.data)[idx], n, per, cap),
                    valid,
                    c.dictionary,
                    c.hash_pool,
                )
            )
        mask = self._shard_split(np.ones(n, dtype=np.bool_), n, per, cap)
        return ShardedPage(list(page.names), cols, mask, self.n_shards)

    def _broadcast_page(self, node) -> Page:
        """Resolve an Exchange(broadcast) — or, in a fleet fragment, a
        RemoteSource standing for a cut broadcast exchange — into one
        local Page (replicated into SPMD programs via a P() in_spec)."""
        if isinstance(node, P.RemoteSource):
            return self._compact(self._RemoteSource(node))
        if node.input_dist == "single":
            return self._compact(self.execute(node.source))
        return self.gather(self.execute_dist(node.source))

    # ---- sharded fused chains -------------------------------------------

    def _sharded_sig(self, sp: ShardedPage) -> tuple:
        return tuple(
            (
                n, repr(c.type), id(c.dictionary),
                None if c.hash_pool is None else c.hash_pool.token,
                c.valid is not None,
            )
            for n, c in zip(sp.names, sp.columns)
        ) + (sp.shard_capacity, self.n_shards)

    def _run_chain_sharded(
        self, chain: list[P.PlanNode], sp: ShardedPage
    ) -> ShardedPage:
        """Chain runner per shard: same fused-pipeline compiler as the
        local executor, wrapped in shard_map so every shard executes the
        one program on its rows (overflow flags and the streamed
        group-by's order check pmax-reduced). A page that arrives
        ordered on a column (``ShardedPage.ordered_on``) says so to the
        chain builder, and the output carries what the chain kept."""
        shard_cap = sp.shard_capacity
        caps = stage.plan_capacities(
            chain, shard_cap, n_shards=self.n_shards,
            ordered_on=sp.ordered_on,
        )
        axis = self.axis
        out_map = None
        if shape_policy.enabled(self.session):
            canon = shape_policy.canonicalize_chain(chain, list(sp.names))
            if canon is not None:
                # nameless normal form: the shard program (and its
                # cache key) goes independent of this query's symbol
                # names — see exec.shapes
                by_name = dict(zip(sp.names, sp.columns))
                sp = ShardedPage(
                    list(canon.in_map.values()),
                    [by_name[o] for o in canon.in_map],
                    sp.mask, self.n_shards,
                    ordered_on=canon.in_map.get(sp.ordered_on),
                )
                chain, out_map = canon.chain, canon.out_map
        chain_key = tuple(self._node_key(n) for n in chain)
        # where ``note_chain_flags`` remembers, as for a local chain,
        # that this one's input broke its declared order
        caps_key = ("caps", chain_key, self._sharded_sig(sp))
        while True:
            if sp.ordered_on is not None and dict.get(
                self._jit_cache, _unordered_key(caps_key)  # no program
            ):
                sp = dc_replace(sp, ordered_on=None)
            key = (
                "mesh-chain",
                chain_key,
                tuple((i, c[0]) for i, c in sorted(caps.items())),
                self._sharded_sig(sp),
                # an Aggregate over this key groups by runs: another program
                sp.ordered_on,
            )
            hit = self._mesh_jit_cache.get(key)
            if hit is None:
                in_layout = stage.ChainLayout(
                    names=list(sp.names),
                    types={n: c.type for n, c in zip(sp.names, sp.columns)},
                    dicts={
                        n: c.dictionary
                        for n, c in zip(sp.names, sp.columns)
                    },
                    capacity=shard_cap,
                    pools={
                        n: c.hash_pool
                        for n, c in zip(sp.names, sp.columns)
                        if c.hash_pool is not None
                    },
                    ordered_on=sp.ordered_on,
                )
                fn, out_layout = stage.build_chain(chain, in_layout, caps)
                leaves, meta = _page_leaves(sp)

                tail = stage.op_scope(len(chain) - 1, chain[-1])

                def flat_fn(*ls, _fn=fn, _meta=meta):
                    env, mask = _env_from_leaves(list(ls), _meta)
                    env2, mask2, flags = _fn(env, mask)
                    # the flags' reduction over shards is the last
                    # operator's
                    with jax.named_scope(tail):
                        flags = {
                            k: jax.lax.pmax(v.astype(jnp.int32), axis)
                            for k, v in flags.items()
                        }
                    return env2, mask2, flags

                def flat_fn_shape(*ls, _fn=fn, _meta=meta):
                    # structure-only twin of flat_fn (pmax needs the
                    # mesh axis, which eval_shape doesn't provide)
                    env, mask = _env_from_leaves(list(ls), _meta)
                    return _fn(env, mask)

                leaf_shapes = [
                    jax.ShapeDtypeStruct(
                        (l.shape[0] // self.n_shards,) + l.shape[1:], l.dtype
                    )
                    for l in leaves
                ]
                out_shape = jax.eval_shape(flat_fn_shape, *leaf_shapes)
                out_specs = (
                    jax.tree.map(lambda _: PS(axis), out_shape[0]),
                    PS(axis),
                    jax.tree.map(lambda _: PS(), out_shape[2]),
                )
                prog = self._shard_jit(
                    flat_fn, _chain_program_name(chain),
                    (PS(axis),) * len(leaves), out_specs,
                    entry=stage.op_scope(0, chain[0]),
                )
                hit = (prog, out_layout, meta)
                self._mesh_jit_cache[key] = hit
                # catalog the sharded program at its full (unsharded)
                # leaf avals — lowering there reuses the same jit trace
                program_catalog.CATALOG.register(
                    key, source="mesh",
                    label="→".join(type(n).__name__ for n in chain),
                    resolver=program_catalog.aot_resolver(
                        prog,
                        tuple(
                            jax.ShapeDtypeStruct(l.shape, l.dtype)
                            for l in leaves
                        ),
                    ),
                )
                t_compile = time.perf_counter()
            else:
                program_catalog.CATALOG.note_hit(key)
                t_compile = None
            prog, out_layout, meta = hit
            leaves, _ = _page_leaves(sp)
            # each grouped Aggregate's path, in chain order, as the
            # local runner notes it (telemetry.span_totals counts them)
            note = {}
            if out_layout.groupbys:
                note["groupbys"] = [
                    path for _pos, path in sorted(out_layout.groupbys.items())
                ]
                note["start_walks"] = sum(out_layout.start_walks.values())
            env, mask, flags = self._run(
                prog, t_compile is not None, *leaves, tag="chain",
                distinct_of=chain, **note
            )
            if t_compile is not None:
                program_catalog.CATALOG.note_compile_seconds(
                    key, time.perf_counter() - t_compile
                )
            if flags:
                with telemetry.child_span("host_sync", site="mesh_chain_flags"):
                    vals = jax.device_get(flags)
                # an overflow grows that table, a tripped order check
                # (some shard's rows are not the runs they were declared
                # to be) bars the chain from grouping by runs
                if self.note_chain_flags(vals, caps_key, caps):
                    continue
            if out_map is not None:
                out_layout, env = _rename_out(out_layout, env, out_map)
            cols = [
                Column(
                    out_layout.types[s],
                    env[s][0],
                    env[s][1],
                    out_layout.dicts.get(s),
                    out_layout.pools.get(s),
                )
                for s in out_layout.names
            ]
            return ShardedPage(
                list(out_layout.names), cols, mask, self.n_shards,
                ordered_on=out_layout.ordered_on,
            )

    # ---- hash exchange ---------------------------------------------------

    def hash_exchange(
        self, sp: ShardedPage, key_symbols: list[str]
    ) -> ShardedPage:
        return self.exchange_by_dest(
            sp, self._hash_dest(sp, key_symbols),
            edge=f"mesh-hash({', '.join(key_symbols)})",
        )

    def _hash_dest(self, sp: ShardedPage, key_symbols: list[str]):
        """The shard every row's key hashes to, as one program."""
        cols = [sp.column(k) for k in key_symbols]
        hashed = tuple(c.hash_pool is not None for c in cols)
        key = (
            "mesh-dest", hashed,
            tuple(
                (c.data.dtype.str, c.data.shape, c.valid is not None)
                for c in cols
            ),
        )
        prog = self._mesh_jit_cache.get(key)
        miss = prog is None
        if miss:
            n = self.n_shards

            def fd(*pairs):
                h = K.hash_columns(_exchange_key_pairs(
                    [(d, v, hd) for (d, v), hd in zip(pairs, hashed)]
                ))
                return (h % jnp.uint64(n)).astype(jnp.int32)

            prog = _named_jit(fd, "mesh_exchange_dest")
            self._mesh_jit_cache[key] = prog
        return self._run(prog, miss, *[(c.data, c.valid) for c in cols])

    #: most rows of a shard's leading run that ``exchange_in_place``
    #: hands to the shard before it (one order's lines, one PARTIAL
    #: row a key: a seam cuts a run of a few rows); a longer run takes
    #: the hash exchange
    IN_PLACE_BUCKET = 128

    def exchange_in_place(self, sp: ShardedPage) -> ShardedPage:
        """The hash exchange on the key the page is ranged and ordered
        on (``sp.ordered_on``), satisfied where the rows lie. The
        exchange's contract — afterwards every key's live rows are on
        one shard and every live row is conserved — holds already but
        for a key whose run a shard boundary cuts: a shard whose
        leading run continues its predecessor's last key hands that
        run over (one neighbour ``ppermute`` of a bucket of
        ``IN_PLACE_BUCKET`` rows a leaf), the receiver appends it to
        its live prefix, the sender shifts its rows left by what it
        sent. Contiguous copies only; each shard's live rows stay an
        ascending prefix, so the page keeps ``ordered_on``.

        The order is a declaration, so the program checks what it
        relies on — every shard's live rows an ascending prefix, no
        shard starting below the rows before it, no key on more than
        two adjacent shards, the run within the bucket, room behind
        the receiver's rows — and returns one flag with its counts;
        where that is set (or the key is nullable or wider than one
        lane) the rows go through ``hash_exchange`` as they came. One
        ``mesh-exchange`` span either way: it is the one exchange the
        plan asked for (``in_place`` says which way it went)."""
        k = sp.ordered_on
        col = sp.column(k)
        if col.valid is not None or col.data.ndim != 1:
            return self.hash_exchange(sp, [k])
        edge = f"mesh-hash({k})"
        with telemetry.child_span("mesh-exchange", edge=edge) as span:
            out = self._exchange_in_place(sp, edge, span)
            if out is None:
                out = self._exchange_by_dest(
                    sp, self._hash_dest(sp, [k]), edge, span
                )
            return out

    def _exchange_in_place(
        self, sp: ShardedPage, edge: str, span
    ) -> ShardedPage | None:
        n, cap = self.n_shards, sp.shard_capacity
        bucket = min(self.IN_PLACE_BUCKET, cap)
        leaves, meta = _page_leaves(sp)
        key_at = next(
            i for i, l in enumerate(leaves)
            if l is sp.column(sp.ordered_on).data
        )
        key = (
            "mesh-exchange-in-place",
            tuple((l.dtype.str, l.shape) for l in leaves), key_at, bucket,
        )
        prog = self._mesh_jit_cache.get(key)
        miss = prog is None
        if miss:
            axis = self.axis

            def fn(*ls):
                return seam_exchange(
                    K.normalize_key(ls[key_at], None)[0], ls[-1],
                    list(ls[:-1]), n, bucket, axis,
                )

            prog = self._shard_jit(
                fn, "exchange_in_place",
                (PS(axis),) * len(leaves),
                ([PS(axis)] * (len(leaves) - 1), PS(axis), PS()),
            )
            self._mesh_jit_cache[key] = prog
        out, new_live, stat = self._run(
            prog, miss, *leaves, tag="exchange"
        )
        with telemetry.child_span("host_sync", site="mesh_exchange_flag"):
            stat = np.asarray(jax.device_get(stat))
        if stat[0]:
            return None
        moved = int(stat[1])
        row_bytes = sum(
            int(np.prod(l.shape[1:])) * l.dtype.itemsize for l in leaves
        )
        sent_bytes = n * bucket * (row_bytes - sp.mask.dtype.itemsize)
        self.exchange_stats["exchanges"] += 1
        self.exchange_stats["bytes"] += sent_bytes
        telemetry.EXCHANGE_BYTES.inc(sent_bytes)
        if span is not None:
            span.attrs.update(
                live_rows=moved, live_bytes=moved * row_bytes,
                buffer_bytes=sent_bytes, escalations=0, in_place=True,
            )
        self._observe_exchange(
            edge, sp.mask, new_live, lambda: stat[2:],
            f"{n}-shard in-place exchange, bucket={bucket}",
        )
        return ShardedPage(
            list(sp.names), _columns_from_leaves(out, meta, sp.columns),
            new_live, n, ordered_on=sp.ordered_on,
        )

    def range_exchange(
        self, sp: ShardedPage, sort_keys
    ) -> ShardedPage:
        """Distributed-sort shuffle: rows route to shards by sampled
        splitters of the first sort key, so shard-local sorts
        concatenate into global order (the range-partitioned analog of
        the reference's merge exchange, MAIN/operator/MergeOperator.java
        — instead of merging sorted streams on one node, the engine
        makes shard ranges disjoint up front; equal keys colocate, so
        ties never straddle a shard boundary)."""
        k = sort_keys[0]
        col = sp.column(k.symbol)
        if col.hash_pool is not None:
            raise AssertionError(
                "range exchange over a hash-coded varchar key (the "
                "stats gate must keep ORDER BY columns dictionary-coded)"
            )
        nulls_first = (
            k.nulls_first if k.nulls_first is not None else not k.ascending
        )
        # splitters from a strided sample (the runtime analog of the
        # reference's DeterminePartitionCount + writer rebalancing:
        # quantiles of the observed key distribution)
        stride = max(int(sp.mask.shape[0]) // 4096, 1)
        key = ("mesh-range-bits", self._sharded_sig(sp), k.symbol,
               k.ascending, nulls_first)
        prog = self._mesh_jit_cache.get(key)
        miss = prog is None
        if miss:
            def fb(data, valid, mask):
                d = data[:, 0] if data.ndim == 2 else data
                bits = K.order_bits(d)
                if not k.ascending:
                    bits = ~bits
                if valid is not None:
                    sentinel = (
                        jnp.uint64(0) if nulls_first
                        else jnp.uint64(0xFFFFFFFFFFFFFFFF)
                    )
                    bits = jnp.where(valid, bits, sentinel)
                return bits, bits[::stride], mask[::stride]

            prog = _named_jit(fb, "mesh_range_bits")
            self._mesh_jit_cache[key] = prog
        bits, sample_dev, live_dev = self._run(
            prog, miss, col.data, col.valid, sp.mask
        )
        with telemetry.child_span("host_sync", site="mesh_range_sample"):
            sample, live = jax.device_get((sample_dev, live_dev))
        sample = sample[live]
        if len(sample) == 0:
            # every row dead: any destination conserves them
            qs = np.zeros(self.n_shards - 1, dtype=np.uint64)
        else:
            qs = np.quantile(
                np.sort(sample),
                [i / self.n_shards for i in range(1, self.n_shards)],
                method="nearest",
            ).astype(np.uint64)
        prog_d = self._mesh_jit_cache.get("range-dest")
        miss = prog_d is None
        if miss:
            def range_dest(splitters, bits_):
                return jnp.searchsorted(
                    splitters, bits_, side="right"
                ).astype(jnp.int32)

            prog_d = _named_jit(K.kernel(range_dest), "mesh_range_dest")
            self._mesh_jit_cache["range-dest"] = prog_d
        dest = self._run(prog_d, miss, qs, bits)
        return self.exchange_by_dest(
            sp, dest, edge=f"mesh-range({k.symbol})"
        )

    def exchange_by_dest(
        self, sp: ShardedPage, dest: jnp.ndarray,
        edge: str = "mesh-exchange",
    ) -> ShardedPage:
        """Route every live row to the shard named by ``dest`` — the
        engine's shuffle: one all_to_all over ICI, with bucket-overflow
        retry (the OutputBuffer backpressure analog). ``edge`` names
        the exchange for the ``check_exchange_coverage`` debug
        assertion (live rows must be conserved across the shuffle).

        The whole of it is one ``mesh-exchange`` span: ``live_rows`` is
        the count the program returns beside its overflow flag (read
        in the same transfer), ``live_bytes`` those rows at the width
        of one row of every leaf (what had to move), ``buffer_bytes``
        the padded buffers the all_to_all is given (what
        ``exchange_stats`` counts), ``escalations`` the bucket
        retries."""
        with telemetry.child_span("mesh-exchange", edge=edge) as span:
            return self._exchange_by_dest(sp, dest, edge, span)

    def _exchange_by_dest(
        self, sp: ShardedPage, dest: jnp.ndarray, edge: str, span
    ) -> ShardedPage:
        shard_cap = sp.shard_capacity
        n = self.n_shards
        bucket_cap = shape_policy.exchange_bucket(shard_cap, n)
        leaves, meta = _page_leaves(sp)
        self.exchange_stats["exchanges"] += 1
        moved = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves
        )
        self.exchange_stats["bytes"] += moved
        telemetry.EXCHANGE_BYTES.inc(moved)
        row_bytes = sum(
            int(np.prod(l.shape[1:])) * l.dtype.itemsize for l in leaves
        )
        escalations = 0
        while True:
            key = (
                "mesh-exchange",
                tuple((l.dtype.str, l.shape) for l in leaves),
                bucket_cap,
            )
            prog = self._mesh_jit_cache.get(key)
            miss = prog is None
            if miss:
                axis = self.axis

                def fn(dest_, *ls):
                    live = ls[-1]
                    payload = {str(i): a for i, a in enumerate(ls[:-1])}
                    recv, rlive, ovf = partition_exchange(
                        dest_, live, payload, n, bucket_cap, axis
                    )
                    ovf = jax.lax.pmax(ovf.astype(jnp.int32), axis)
                    n_live = jax.lax.psum(
                        jnp.sum(live.astype(jnp.int32)), axis
                    )
                    out = [recv[str(i)] for i in range(len(ls) - 1)]
                    return out, rlive, jnp.stack([ovf, n_live])

                prog = self._shard_jit(
                    fn, "exchange",
                    (PS(axis),) * (len(leaves) + 1),
                    ([PS(axis)] * (len(leaves) - 1), PS(axis), PS()),
                )
                self._mesh_jit_cache[key] = prog
            out, rlive, stat = self._run(
                prog, miss, dest, *leaves, tag="exchange"
            )
            with telemetry.child_span("host_sync", site="mesh_exchange_flag"):
                ovf, n_live = (int(v) for v in jax.device_get(stat))
            if ovf and bucket_cap < shard_cap:
                self.exchange_escalations += 1
                escalations += 1
                bucket_cap = min(bucket_cap * 4, shard_cap)
                continue
            if ovf:
                raise SkewOverflow(
                    "exchange bucket overflow at max capacity"
                )
            if span is not None:
                span.attrs.update(
                    live_rows=n_live, live_bytes=n_live * row_bytes,
                    buffer_bytes=moved, escalations=escalations,
                )
            cols = _columns_from_leaves(out, meta, sp.columns)
            def dest_counts():
                with telemetry.child_span(
                    "host_sync", site="mesh_partition_counters"
                ):
                    d_host, live_host = jax.device_get((dest, sp.mask))
                return np.bincount(
                    np.asarray(d_host).ravel()[
                        np.asarray(live_host).ravel().astype(bool)
                    ],
                    minlength=n,
                )

            self._observe_exchange(
                edge, sp.mask, rlive, dest_counts,
                f"{self.n_shards}-shard all_to_all, bucket_cap={bucket_cap}",
            )
            return ShardedPage(list(sp.names), cols, rlive, self.n_shards)

    def _observe_exchange(
        self, edge: str, live_in, live_out, dest_counts, detail: str
    ) -> None:
        """What every exchange owes its observers once its rows are
        placed: the per-destination row counters (``dest_counts()``
        gives them, asked only when this exchange is counted) and the
        ``check_exchange_coverage`` assertion over the live masks
        before and after."""
        from trino_tpu import session_properties as SP

        count_now = bool(
            SP.get(self.session, "exchange_partition_counters")
        )
        if not count_now:
            # sampled mode: count every Nth all_to_all instead of
            # every one. The host sync the counters force costs the
            # whole dispatch pipeline, so exact counting taxes
            # every exchange; 1/N sampling keeps skew observability
            # on by default at 1/N of that tax. Tradeoff: absolute
            # rows under-report by ~N (the metric is a sample, not
            # a census) but max/mean and cv are preserved in
            # expectation — hot-partition DETECTION survives
            # sampling, exact conservation accounting does not.
            n_sample = int(
                SP.get(
                    self.session, "exchange_partition_counter_sample"
                )
            )
            if n_sample > 0:
                seq = getattr(self, "_exchange_count_seq", 0)
                self._exchange_count_seq = seq + 1
                count_now = (seq % n_sample) == 0
        if count_now:
            # skew observability (forces a host sync, so gated the
            # same way as the coverage check): per-destination live
            # row counts for this named edge, folded into
            # exchange_stats histograms and the
            # trino_exchange_partition_rows metric family
            hist = self.exchange_stats.setdefault(
                "partition_rows", {}
            ).setdefault(edge, {})
            for p, c in enumerate(dest_counts()):
                if c:
                    hist[p] = hist.get(p, 0) + int(c)
                    telemetry.EXCHANGE_PARTITION_ROWS.inc(
                        int(c), edge=edge, partition=str(p)
                    )
        if SP.get(self.session, "check_exchange_coverage"):
            # debug assertion (forces a host sync): an exchange
            # must conserve live rows — any loss here is exactly
            # the mesh×fleet wrong-results class, attributed to
            # this named edge instead of surfacing as a silently
            # short result
            from trino_tpu.plan.validate import ExchangeCoverageError

            with telemetry.child_span(
                "host_sync", site="mesh_exchange_coverage"
            ):
                n_in, n_out = jax.device_get((
                    jnp.sum(live_in.astype(jnp.int32)),
                    jnp.sum(live_out.astype(jnp.int32)),
                ))
            if int(n_in) != int(n_out):
                raise ExchangeCoverageError(
                    edge, int(n_in), int(n_out), detail=detail
                )

    # ---- distributed joins ----------------------------------------------

    def _unify_key_dicts(self, left, right, criteria) -> None:
        """Remap varchar join keys onto shared dictionaries BEFORE any
        hashing, so co-partitioning routes equal strings to the same
        shard regardless of which table they came from."""
        for ls, rs in criteria:
            lc, rc = left.column(ls), right.column(rs)
            if lc.hash_pool is not None and rc.hash_pool is not None:
                # hash codes are globally consistent; only the
                # cross-pool injectivity proof is needed
                lc.hash_pool.verify_joinable(rc.hash_pool)
                continue
            if lc.dictionary is not None or rc.dictionary is not None:
                lc2, rc2 = unify_dictionaries(lc, rc)
                left.columns[left.names.index(ls)] = lc2
                right.columns[right.names.index(rs)] = rc2

    def _dist_join(self, node: P.Join) -> ShardedPage:
        if node.kind == "cross":
            return self._dist_cross(node)
        if not node.criteria:
            # non-equi join: no key to co-partition on — gather both
            # sides and run the local nested-loop path, then re-shard
            # (the reference replicates the build side into
            # NestedLoopJoinOperator; at this shape the local tier is
            # the honest cost)
            if node.kind == "right":
                node = P.Join(
                    node.outputs, kind="left", left=node.right,
                    right=node.left, criteria=[], filter=node.filter,
                    df_range_keep=None, df_keep_frac=None,
                )

            def page_of(n: P.PlanNode) -> Page:
                if isinstance(n, P.RemoteSource):
                    return self._RemoteSource(n)
                if (
                    isinstance(n, P.Exchange)
                    and n.partitioning == "broadcast"
                ):
                    return self._broadcast_page(n)
                return self.gather(self.execute_dist(n))

            return self.scatter(
                self._nested_loop_join(
                    node,
                    self._compact(page_of(node.left)),
                    self._compact(page_of(node.right)),
                )
            )
        kind, criteria = node.kind, list(node.criteria)
        if node.distribution == "BROADCAST":
            probe = self.execute_dist(node.left)
            build = self._broadcast_page(node.right)
            self._unify_key_dicts(probe, build, criteria)
            if kind == "inner":
                probe = self._dynamic_filter_sharded(
                    node, probe, build, criteria
                )
            replicated = True
        else:
            left = self.execute_dist(node.left)
            right = self.execute_dist(node.right)
            if kind == "right":
                left, right = right, left
                criteria = [(b, a) for a, b in criteria]
                kind = "left"
            self._unify_key_dicts(left, right, criteria)
            if kind == "inner":
                # prune BEFORE the all_to_all: fewer exchanged rows and
                # smaller co-partitioned shard capacities
                left = self._dynamic_filter_sharded(
                    node, left, right, criteria
                )
            out = None
            if kind == "inner" and self._probe_is_skewed(left, criteria):
                out = self._skew_join(node, left, right, criteria)
            if out is not None:
                return out
            try:
                probe = self.hash_exchange(left, [a for a, _ in criteria])
                build = self.hash_exchange(right, [b for _, b in criteria])
            except SkewOverflow:
                if kind != "inner":
                    raise
                out = self._skew_join(node, left, right, criteria)
                if out is None:
                    raise
                return out
            replicated = False
        out_syms = list(node.outputs)
        if kind == "right":
            # BROADCAST right joins never occur (distribute forces
            # PARTITIONED), so kind is inner/left/full here
            raise AssertionError("unflipped right join in mesh executor")
        return self._equi_join_sharded(
            node, probe, build, replicated, kind, criteria, out_syms
        )

    def _dynamic_filter_sharded(
        self, node: P.Join, probe: ShardedPage, build, criteria
    ) -> ShardedPage:
        """Distributed dynamic filtering (DynamicFilterService analog,
        MAIN/server/DynamicFilterService.java:106): prune probe rows
        whose join key matches NO build row, BEFORE the all_to_all —
        fewer exchanged rows and smaller co-partitioned shard
        capacities. Inner joins only (callers enforce).

        Unlike the reference's min/max + bloom domains, the filter here
        is an exact membership probe (sort + searchsorted — join phase
        A reused as a filter): only the build KEY column crosses shards
        (all_gather of one column vs exchanging every probe column),
        and uniform dense keys — where min/max never prunes — still
        drop. Multi-key criteria use the hash-combined key, so false
        positives pass through harmlessly to the real join."""
        from trino_tpu import session_properties as SP

        if not SP.get(self.session, "dynamic_filtering_enabled"):
            return probe
        axis = self.axis
        if probe.shard_capacity * probe.n_shards < self.DF_MIN_PROBE:
            return probe
        # planner hint: expected keep fraction under membership — a
        # near-1.0 keep means the probe pass is pure cost
        if node.df_keep_frac is None or node.df_keep_frac > 0.7:
            return probe
        replicated = not isinstance(build, ShardedPage)
        p_leaves, p_meta = _page_leaves(probe)
        b_leaves, b_meta = _page_leaves(build)
        n_p = len(p_leaves)
        kinds = self._join_key_kinds(probe, build, criteria)
        key_lo, key_bits = self._join_key_width(
            node.key_ranges, criteria, probe, build
        )
        prelude = _make_prelude(
            criteria, p_meta, b_meta, n_p, len(criteria) > 1, kinds, key_lo
        )
        leaves = p_leaves + b_leaves
        key_b = (
            "mesh-df", tuple(criteria), key_lo, key_bits,
            self._sharded_sig(probe), self._join_sig(build, replicated),
        )
        prog_b = self._mesh_jit_cache.get(key_b)
        miss = prog_b is None
        if miss:
            def fk(*ls):
                (_, p_mask, _, _, pk, bk, probe_live, build_live,
                 _, _) = prelude(ls)
                if not replicated:
                    bk = jax.lax.all_gather(bk, axis, tiled=True)
                    build_live = jax.lax.all_gather(
                        build_live, axis, tiled=True
                    )
                _, _, cnt = K.join_ranges(
                    bk, build_live, pk, probe_live, key_bits=key_bits
                )
                keep = probe_live & (cnt > 0)
                n_in = jnp.sum(p_mask.astype(jnp.int32)).reshape(1)
                n_keep = jnp.sum(keep.astype(jnp.int32)).reshape(1)
                return keep, n_in, n_keep

            prog_b = self._shard_jit(
                fk, "dynamic_filter",
                (PS(axis),) * n_p + (
                    (PS(),) if replicated else (PS(axis),)
                ) * len(b_leaves),
                (PS(axis), PS(axis), PS(axis)),
            )
            self._mesh_jit_cache[key_b] = prog_b
        keep, n_in_dev, n_keep_dev = self._run(
            prog_b, miss, *leaves, tag="dynamic-filter",
            join_build=(
                build.capacity if replicated
                else build.shard_capacity * build.n_shards  # all-gathered
            ),
            key_bits=key_bits,
        )
        with telemetry.child_span("host_sync", site="mesh_dynamic_filter"):
            n_in, n_keep = jax.device_get((n_in_dev, n_keep_dev))
        in_rows, kept = int(n_in.sum()), int(n_keep.sum())
        self.df_log.append(
            {"rows_in": in_rows, "rows_kept": kept, "pairs": list(criteria)}
        )
        del self.df_log[:-100]  # bounded: executors outlive queries
        if kept > (1.0 - self.DF_MIN_DROP) * max(in_rows, 1):
            return probe
        new_cap = pad_capacity(int(max(n_keep.max(), 1)))
        if new_cap >= probe.shard_capacity:
            # no capacity win; still use the narrowed mask
            return ShardedPage(
                list(probe.names), list(probe.columns), keep, probe.n_shards
            )
        key_c = ("mesh-dfC", self._sharded_sig(probe), new_cap)
        prog_c = self._mesh_jit_cache.get(key_c)
        miss = prog_c is None
        if miss:
            def fc(kp, *ls):
                env, _ = _env_from_leaves(list(ls), p_meta)
                return K.compact_rows(env, kp, new_cap)

            prog_c = self._shard_jit(
                fc, "compact",
                (PS(axis),) * (len(p_leaves) + 1), (PS(axis), PS(axis)),
            )
            self._mesh_jit_cache[key_c] = prog_c
        env, new_mask = self._run(prog_c, miss, keep, *p_leaves)
        cols = [
            Column(c.type, *env[n], c.dictionary, c.hash_pool)
            for n, c in zip(probe.names, probe.columns)
        ]
        return ShardedPage(list(probe.names), cols, new_mask, probe.n_shards)

    # ---- skew-split join (SkewedPartitionRebalancer analog,
    # MAIN/operator/output/SkewedPartitionRebalancer.java — but for
    # joins, which the reference just lets eat the skew) --------------

    #: probes below this skip the skew histogram (one tiny dispatch)
    SKEW_MIN_PROBE = 1 << 16
    #: a destination this many times above the mean marks skew
    SKEW_FACTOR = 4.0

    def _probe_is_skewed(self, probe: ShardedPage, criteria) -> bool:
        """One cheap histogram dispatch: is any exchange destination
        loaded far beyond the mean? Without the split, a hot key
        inflates every shard's received capacity (n_shards x bucket)
        and serializes the whole mesh behind one shard's join. The
        (dest, counts) pair memoizes for _skew_join's immediate reuse."""
        if probe.shard_capacity * probe.n_shards < self.SKEW_MIN_PROBE:
            return False
        keys = tuple(a for a, _ in criteria)
        dest, counts = self._dest_counts(probe, list(keys))
        total = counts.sum()
        if total == 0:
            return False
        mean = total / self.n_shards
        skewed = bool(counts.max() > self.SKEW_FACTOR * mean)
        # memoize for _skew_join's immediate reuse only — holding the
        # page/dest arrays any longer would pin device memory
        self._dest_memo = (
            (id(probe), keys, probe, dest, counts) if skewed else None
        )
        return skewed

    def _dest_counts_memo(self, sp: ShardedPage, key_syms: list[str]):
        memo = getattr(self, "_dest_memo", None)
        self._dest_memo = None  # one-shot: never pin device arrays
        if (
            memo is not None
            and memo[0] == id(sp)
            and memo[1] == tuple(key_syms)
            and memo[2] is sp
        ):
            return memo[3], memo[4]
        return self._dest_counts(sp, key_syms)

    def _dest_counts(self, sp: ShardedPage, key_syms: list[str]):
        """(dest per row, global per-destination row counts)."""
        dest = self._hash_dest(sp, key_syms)
        prog = self._mesh_jit_cache.get("dest-hist")
        miss = prog is None
        if miss:
            n = self.n_shards

            def dest_hist(d, m):
                return jax.ops.segment_sum(
                    jnp.where(m, 1, 0), d, num_segments=n
                )

            prog = _named_jit(K.kernel(dest_hist), "mesh_dest_hist")
            self._mesh_jit_cache["dest-hist"] = prog
        counts = self._run(prog, miss, dest, sp.mask)
        with telemetry.child_span("host_sync", site="mesh_dest_counts"):
            return dest, np.asarray(jax.device_get(counts))

    def _skew_join(
        self, node: P.Join, left: ShardedPage, right: ShardedPage,
        criteria,
    ) -> ShardedPage | None:
        """Inner join under destination skew: split the BUILD side into
        hot destinations (broadcast to every shard) and cold ones
        (hash-partitioned as usual); hot PROBE rows salt round-robin
        across the mesh. The two joins partition the key space
        disjointly, so their union is the exact join — a hot key's
        probe rows spread over all shards instead of escalating one
        bucket to shard capacity and failing."""
        lkeys = [a for a, _ in criteria]
        rkeys = [b for _, b in criteria]
        p_dest, p_counts = self._dest_counts_memo(left, lkeys)
        b_dest, b_counts = self._dest_counts(right, rkeys)
        shard_cap = left.shard_capacity
        # a destination is hot when either side's load cannot fit the
        # exchange's maximum bucket
        mean = max(p_counts.sum() / self.n_shards, 1.0)
        # hot = destinations overloaded on the PROBE side (what skew
        # salting fixes); a tightly-packed-but-balanced build must not
        # trip this — its per-dest load naturally sits near capacity
        hot = (p_counts > shard_cap // 2) | (
            p_counts > self.SKEW_FACTOR * mean
        )
        # the hot builds replicate to every shard — bail out to the
        # plain exchange when that replica would itself be oversized
        # (the memory blowup this path exists to avoid)
        if (
            not hot.any()
            or b_counts[hot].sum() > 4 * right.shard_capacity
        ):
            return None
        self.skew_joins += 1
        hot_dev = jnp.asarray(hot)

        # probe: hot rows round-robin by global position, cold rows keep
        # their hash destination
        rr = (
            jnp.arange(p_dest.shape[0], dtype=jnp.int32)
            % jnp.int32(self.n_shards)
        )
        salted = jnp.where(hot_dev[p_dest], rr, p_dest)
        probe = self.exchange_by_dest(left, salted)

        # build: cold rows exchange; hot rows gather into one local
        # replicated page (hot keys are few — their build rows fit)
        cold_mask = right.mask & ~hot_dev[b_dest]
        cold = ShardedPage(
            list(right.names), list(right.columns), cold_mask,
            right.n_shards,
        )
        build_cold = self.hash_exchange(cold, rkeys)
        hot_mask = right.mask & hot_dev[b_dest]
        hot_sp = ShardedPage(
            list(right.names), list(right.columns), hot_mask,
            right.n_shards,
        )
        build_hot = self.gather(hot_sp)

        out_syms = list(node.outputs)
        part1 = self._equi_join_sharded(
            node, probe, build_cold, False, "inner", criteria, out_syms
        )
        part2 = self._equi_join_sharded(
            node, probe, build_hot, True, "inner", criteria, out_syms
        )
        return self._concat_sharded(part1, part2)

    def _dist_groupid(self, node: P.GroupId) -> ShardedPage:
        """Shard-local GroupId replication: each shard concatenates k
        masked copies of its rows (no exchange — the aggregation above
        hash-exchanges on (id, keys)). GroupIdOperator analog
        (MAIN/operator/GroupIdOperator.java) in SPMD form."""
        src = self.execute_dist(node.source)
        k = len(node.grouping_sets)
        sets = [tuple(st) for st in node.grouping_sets]
        keyed = set(s for st in sets for s in st)
        leaves, meta = _page_leaves(src)
        axis = self.axis
        key = ("mesh-groupid", self._sharded_sig(src), tuple(sets))
        prog = self._mesh_jit_cache.get(key)
        miss = prog is None
        if miss:
            names = list(src.names)

            def fg(*ls):
                env, mask = _env_from_leaves(ls, meta)
                outs = []
                for name in names:
                    data, valid = env[name]
                    outs.append(jnp.concatenate([data] * k))
                    n = data.shape[0]
                    if name in keyed:
                        vf = (
                            valid if valid is not None
                            else jnp.ones((n,), dtype=jnp.bool_)
                        )
                        none = jnp.zeros((n,), dtype=jnp.bool_)
                        outs.append(jnp.concatenate([
                            vf if name in st else none for st in sets
                        ]))
                    elif valid is not None:
                        outs.append(jnp.concatenate([valid] * k))
                n = mask.shape[0]
                outs.append(jnp.concatenate([
                    jnp.full((n,), i, dtype=jnp.int64) for i in range(k)
                ]))
                outs.append(jnp.concatenate([mask] * k))
                return outs

            n_out = sum(
                2 if (nm in keyed or hv) else 1 for nm, hv in meta
            ) + 2
            prog = self._shard_jit(
                fg, "group_id",
                (PS(axis),) * len(leaves), [PS(axis)] * n_out,
            )
            self._mesh_jit_cache[key] = prog
        out = self._run(prog, miss, *leaves)
        cols, i = [], 0
        names = []
        for (name, has_valid), c in zip(meta, src.columns):
            data = out[i]
            i += 1
            valid = None
            if name in keyed or has_valid:
                valid = out[i]
                i += 1
            names.append(name)
            cols.append(Column(c.type, data, valid, c.dictionary, c.hash_pool))
        names.append(node.id_symbol)
        cols.append(Column(T.BIGINT, out[i]))
        i += 1
        mask = out[i]
        return ShardedPage(names, cols, mask, src.n_shards)

    def _concat_sharded(self, a: ShardedPage, b: ShardedPage) -> ShardedPage:
        """Per-shard concatenation of two same-layout sharded pages."""
        axis = self.axis
        a_leaves, meta = _page_leaves(a)
        b_leaves, _ = _page_leaves(b)
        key = (
            "mesh-concat", self._sharded_sig(a), self._sharded_sig(b),
        )
        prog = self._mesh_jit_cache.get(key)
        miss = prog is None
        if miss:
            n_a = len(a_leaves)

            def fc(*ls):
                xs, ys = ls[:n_a], ls[n_a:]
                return [
                    jnp.concatenate([x, y]) for x, y in zip(xs, ys)
                ]

            prog = self._shard_jit(
                fc, "concat",
                (PS(axis),) * (len(a_leaves) + len(b_leaves)),
                [PS(axis)] * len(a_leaves),
            )
            self._mesh_jit_cache[key] = prog
        out = self._run(prog, miss, *a_leaves, *b_leaves)
        cols = _columns_from_leaves(out, meta, a.columns)
        return ShardedPage(list(a.names), cols, out[-1], a.n_shards)

    def _match_count_capacity(
        self, key, prelude, in_specs, leaves, b_cap: int, key_bits: int,
        kind: str,
    ) -> int:
        """Phase A of a distributed join: per-shard match totals, one
        host sync, padded output capacity (the build-side barrier).
        ``key_bits``: the width ``prelude``'s keys are ranked at (part
        of ``key`` with their origin, as of every join program's)."""
        prog = self._mesh_jit_cache.get(key)
        miss = prog is None
        if miss:
            axis = self.axis

            def fa(*ls):
                (_, _, _, _, pk, bk, probe_live, build_live, _, _) = (
                    prelude(ls)
                )
                _, _, cnt = K.join_ranges(
                    bk, build_live, pk, probe_live, key_bits=key_bits
                )
                return jnp.sum(cnt).reshape(1)

            prog = self._shard_jit(fa, "join_count", in_specs, PS(axis))
            self._mesh_jit_cache[key] = prog
        totals_dev = self._run(
            prog, miss, *leaves, tag="join-count", join_build=b_cap,
            key_bits=key_bits, join_kind=kind,
        )
        with telemetry.child_span("host_sync", site="mesh_join_total"):
            totals = jax.device_get(totals_dev)
        return pad_capacity(int(max(totals.max(), 1)))

    def _join_sig(self, page, replicated: bool) -> tuple:
        cap = (
            page.shard_capacity
            if isinstance(page, ShardedPage) else page.capacity
        )
        return tuple(
            (
                n, repr(c.type), id(c.dictionary),
                None if c.hash_pool is None else c.hash_pool.token,
                c.valid is not None,
            )
            for n, c in zip(page.names, page.columns)
        ) + (cap, replicated)

    def _equi_join_sharded(
        self, node, probe, build, replicated, kind, criteria, out_syms
    ) -> ShardedPage:
        axis = self.axis
        p_cap = probe.shard_capacity
        b_cap = (
            build.capacity if replicated else build.shard_capacity
        )
        p_leaves, p_meta = _page_leaves(probe)
        b_leaves, b_meta = _page_leaves(build)
        n_p = len(p_leaves)
        p_cols0 = {n: c for n, c in zip(probe.names, probe.columns)}
        kinds = self._join_key_kinds(probe, build, criteria)
        verify = len(criteria) > 1 or any(
            k != "hash" and jnp.ndim(p_cols0[a].data) == 2
            for k, (a, _) in zip(kinds, criteria)
        )
        p_cols = {n: c for n, c in zip(probe.names, probe.columns)}
        b_cols = {n: c for n, c in zip(build.names, build.columns)}
        key_lo, key_bits = self._join_key_width(
            node.key_ranges, criteria, probe, build
        )
        prelude = _make_prelude(
            criteria, p_meta, b_meta, n_p, verify, kinds, key_lo
        )
        in_specs = (PS(axis),) * n_p + (
            (PS(),) if replicated else (PS(axis),)
        ) * len(b_leaves)

        # phase A: per-shard match counts -> one host sync for capacity
        key_a = (
            "mesh-joinA", tuple(criteria), key_lo, key_bits,
            self._join_sig(probe, False), self._join_sig(build, replicated),
        )
        out_cap = self._match_count_capacity(
            key_a, prelude, in_specs, p_leaves + b_leaves, b_cap, key_bits,
            kind,
        )

        # reserve the per-device join working set (probe shard + build
        # + expansion output) through the memory context — sharded
        # joins answer to query_max_memory_per_node like local ones
        lane_bytes = lambda cols: sum(  # noqa: E731
            (2 if jnp.ndim(c.data) == 2 else 1) * 8 for c in cols
        )
        out_row = sum(
            (2 if jnp.ndim((p_cols.get(s) or b_cols[s]).data) == 2
             else 1) * 8
            for s in out_syms
        )
        working_set = (
            p_cap * lane_bytes(probe.columns)
            + b_cap * lane_bytes(build.columns)
            + out_cap * (out_row + 8)
        )
        ctx = self.memory_ctx.child("mesh-join")
        ctx.reserve(working_set)
        ctx.free(working_set)

        # output column metadata
        filter_c = None
        if node.filter is not None:
            filter_c = compile_expr(
                node.filter,
                ColumnLayout(
                    types={s: node.outputs[s] for s in out_syms},
                    dictionaries={
                        s: (p_cols.get(s) or b_cols.get(s)).dictionary
                        for s in out_syms
                    },
                ),
            )
        out_meta = []
        for s in out_syms:
            from_probe = s in p_cols
            col = p_cols[s] if from_probe else b_cols[s]
            has_valid = col.valid is not None
            if kind in ("left", "full") and not from_probe:
                has_valid = True
            if kind == "full" and from_probe:
                has_valid = True
            out_meta.append((s, from_probe, has_valid))

        key_b = (
            "mesh-joinB", tuple(criteria), key_lo, key_bits, kind, out_cap,
            tuple(out_meta), repr(node.filter),
            self._join_sig(probe, False), self._join_sig(build, replicated),
        )
        prog_b = self._mesh_jit_cache.get(key_b)
        miss = prog_b is None
        if miss:
            def fb(*ls):
                (p_env, p_mask, b_env, b_mask,
                 pk, bk, probe_live, build_live, p_bits, b_bits) = (
                    prelude(ls)
                )
                order, lo, cnt = K.join_ranges(
                    bk, build_live, pk, probe_live, key_bits=key_bits
                )
                probe_idx, build_idx, out_live = K.expand_matches(
                    order, lo, cnt, out_cap
                )
                if verify:
                    for pb, bb in zip(p_bits, b_bits):
                        out_live = out_live & K.keys_match(
                            pb, bb, probe_idx, build_idx
                        )
                inner = {}
                for s, from_probe, _ in out_meta:
                    env, idx = (
                        (p_env, probe_idx) if from_probe
                        else (b_env, build_idx)
                    )
                    inner[s] = K.rows_at(*env[s], idx)
                if filter_c is not None:
                    fd, fv = filter_c.fn(inner)
                    out_live = out_live & (
                        fd if fv is None else (fd & fv)
                    )
                col_sections = {
                    s: [inner[s]] for s, _, _ in out_meta
                }
                mask_sections = [out_live]
                if kind in ("left", "full"):
                    matched = K.range_any(cnt, out_live)
                    unmatched = p_mask & ~matched
                    for s, from_probe, _ in out_meta:
                        if from_probe:
                            d, v = p_env[s]
                            col_sections[s].append((d, v))
                        else:
                            d0, _ = b_env[s]
                            # match trailing dims (two-limb decimals
                            # are [n, 2])
                            col_sections[s].append((
                                jnp.zeros(
                                    (p_cap,) + d0.shape[1:],
                                    dtype=d0.dtype,
                                ),
                                jnp.zeros((p_cap,), dtype=jnp.bool_),
                            ))
                    mask_sections.append(unmatched)
                if kind == "full":
                    bmatched = K.scatter_any(build_idx, out_live, b_cap)
                    bunmatched = b_mask & ~bmatched
                    for s, from_probe, _ in out_meta:
                        if from_probe:
                            d0, _ = p_env[s]
                            col_sections[s].append((
                                jnp.zeros(
                                    (b_cap,) + d0.shape[1:],
                                    dtype=d0.dtype,
                                ),
                                jnp.zeros((b_cap,), dtype=jnp.bool_),
                            ))
                        else:
                            d, v = b_env[s]
                            col_sections[s].append((d, v))
                    mask_sections.append(bunmatched)
                outs = []
                for s, _, has_valid in out_meta:
                    parts = col_sections[s]
                    data = jnp.concatenate([d for d, _ in parts])
                    outs.append(data)
                    if has_valid:
                        vs = [
                            (jnp.ones(d.shape[0], dtype=jnp.bool_)
                             if v is None else v)
                            for d, v in parts
                        ]
                        outs.append(jnp.concatenate(vs))
                return outs, jnp.concatenate(mask_sections)

            n_out = sum(2 if hv else 1 for _, _, hv in out_meta)
            prog_b = self._shard_jit(
                fb, "join_expand", in_specs,
                ([PS(axis)] * n_out, PS(axis)),
            )
            self._mesh_jit_cache[key_b] = prog_b
        outs, mask = self._run(
            prog_b, miss, *p_leaves, *b_leaves, tag="join-expand",
            join_build=b_cap, key_bits=key_bits,
        )
        cols, i = [], 0
        for s, from_probe, has_valid in out_meta:
            src = p_cols[s] if from_probe else b_cols[s]
            data = outs[i]
            i += 1
            valid = None
            if has_valid:
                valid = outs[i]
                i += 1
            cols.append(Column(node.outputs[s], data, valid, src.dictionary, src.hash_pool))
        return ShardedPage(
            [s for s, _, _ in out_meta], cols, mask, self.n_shards
        )

    def _dist_cross(self, node: P.Join) -> ShardedPage:
        probe = self.execute_dist(node.left)
        build = self._broadcast_page(node.right)
        nb = build.num_rows()
        axis = self.axis
        p_leaves, p_meta = _page_leaves(probe)
        b_leaves, b_meta = _page_leaves(build)
        n_p = len(p_leaves)
        # phase A: max live probe rows on any shard
        key_a = ("mesh-crossA", self._join_sig(probe, False))
        prog_a = self._mesh_jit_cache.get(key_a)
        miss = prog_a is None
        if miss:
            def fa(mask):
                return jnp.sum(mask.astype(jnp.int32)).reshape(1)

            prog_a = self._shard_jit(
                fa, "cross_count", (PS(axis),), PS(axis)
            )
            self._mesh_jit_cache[key_a] = prog_a
        live_dev = self._run(prog_a, miss, probe.mask)
        with telemetry.child_span("host_sync", site="mesh_cross_count"):
            lmax = int(jax.device_get(live_dev).max())
        out_cap = pad_capacity(max(lmax * nb, 1))
        p_cols = {n: c for n, c in zip(probe.names, probe.columns)}
        b_cols = {n: c for n, c in zip(build.names, build.columns)}
        out_meta = [
            (s, s in p_cols,
             (p_cols.get(s) or b_cols.get(s)).valid is not None)
            for s in node.outputs
        ]
        key_b = (
            "mesh-crossB", out_cap, nb, tuple(out_meta),
            self._join_sig(probe, False), self._join_sig(build, True),
        )
        prog_b = self._mesh_jit_cache.get(key_b)
        miss = prog_b is None
        if miss:
            def fb(*ls):
                p_env, p_mask = _env_from_leaves(list(ls[:n_p]), p_meta)
                b_env, b_mask = _env_from_leaves(list(ls[n_p:]), b_meta)
                n_live = jnp.sum(p_mask.astype(jnp.int32))
                perm = K.compact_perm(p_mask)
                p_cap = p_mask.shape[0]
                b_cap = b_mask.shape[0]
                j = jnp.arange(out_cap)
                li = perm[jnp.clip(j // max(nb, 1), 0, p_cap - 1)]
                ri = jnp.clip(j % max(nb, 1), 0, b_cap - 1)
                out_live = j < n_live * nb
                outs = []
                for s, from_probe, has_valid in out_meta:
                    env, idx = (p_env, li) if from_probe else (b_env, ri)
                    d, v = K.rows_at(*env[s], idx)
                    outs.append(d)
                    if has_valid:
                        outs.append(
                            jnp.ones(out_cap, dtype=jnp.bool_)
                            if v is None else v
                        )
                return outs, out_live

            n_out = sum(2 if hv else 1 for _, _, hv in out_meta)
            in_specs = (PS(axis),) * n_p + (PS(),) * len(b_leaves)
            prog_b = self._shard_jit(
                fb, "cross_join", in_specs,
                ([PS(axis)] * n_out, PS(axis)),
            )
            self._mesh_jit_cache[key_b] = prog_b
        outs, mask = self._run(
            prog_b, miss, *p_leaves, *b_leaves, tag="join-expand"
        )
        cols, i = [], 0
        for s, from_probe, has_valid in out_meta:
            src = p_cols[s] if from_probe else b_cols[s]
            data = outs[i]
            i += 1
            valid = None
            if has_valid:
                valid = outs[i]
                i += 1
            cols.append(Column(node.outputs[s], data, valid, src.dictionary, src.hash_pool))
        return ShardedPage(
            [s for s, _, _ in out_meta], cols, mask, self.n_shards
        )

    # ---- distributed semi join ------------------------------------------

    def _dist_semi(self, node: P.SemiJoin) -> ShardedPage:
        kind = self._take_negated_match(node)
        sp = self.execute_dist(node.source)
        filt = self._broadcast_page(node.filter_source)
        self._unify_key_dicts(sp, filt, node.keys)
        key_nullable = any(
            sp.column(a).valid is not None for a, _ in node.keys
        ) or any(
            filt.column(b).valid is not None for _, b in node.keys
        )
        if node.null_aware and key_nullable:
            # 3VL NULL semantics need global build-NULL knowledge and
            # host-driven per-probe set checks: run the single-device
            # path on gathered rows, then re-shard the result
            page = self.gather(sp)
            return self.scatter(
                self._semi_join_pages(node, page, filt, kind)
            )
        axis = self.axis
        p_leaves, p_meta = _page_leaves(sp)
        b_leaves, b_meta = _page_leaves(filt)
        n_p = len(p_leaves)
        criteria = list(node.keys)
        kinds = self._join_key_kinds(sp, filt, criteria)
        verify = len(criteria) > 1 or any(
            k != "hash" and jnp.ndim(sp.column(a).data) == 2
            for k, (a, _) in zip(kinds, criteria)
        )
        needs_expand = verify or node.filter is not None
        p_cap = sp.shard_capacity
        in_specs = (PS(axis),) * n_p + (PS(),) * len(b_leaves)

        filter_c = None
        if node.filter is not None:
            pair_types = {
                **{n: c.type for n, c in zip(sp.names, sp.columns)},
                **{n: c.type for n, c in zip(filt.names, filt.columns)},
            }
            pair_dicts = {
                **{n: c.dictionary for n, c in zip(sp.names, sp.columns)},
                **{
                    n: c.dictionary
                    for n, c in zip(filt.names, filt.columns)
                },
            }
            filter_c = compile_expr(
                node.filter,
                ColumnLayout(types=pair_types, dictionaries=pair_dicts),
            )

        key_lo, key_bits = self._join_key_width(
            node.key_ranges, criteria, sp, filt
        )
        prelude = _make_prelude(
            criteria, p_meta, b_meta, n_p, verify, kinds, key_lo
        )
        out_cap = None
        if needs_expand:
            key_a = (
                "mesh-semiA", tuple(criteria), key_lo, key_bits,
                self._join_sig(sp, False), self._join_sig(filt, True),
            )
            out_cap = self._match_count_capacity(
                key_a, prelude, in_specs, p_leaves + b_leaves, filt.capacity,
                key_bits, kind,
            )

        key_b = (
            "mesh-semiB", tuple(criteria), key_lo, key_bits, out_cap,
            repr(node.filter),
            self._join_sig(sp, False), self._join_sig(filt, True),
        )
        prog_b = self._mesh_jit_cache.get(key_b)
        miss = prog_b is None
        if miss:
            def fb(*ls):
                (p_env, p_mask, b_env, b_mask,
                 pk, bk, probe_live, build_live, p_bits, b_bits) = (
                    prelude(ls)
                )
                order, lo, cnt = K.join_ranges(
                    bk, build_live, pk, probe_live, key_bits=key_bits
                )
                if needs_expand:
                    probe_idx, build_idx, out_live = K.expand_matches(
                        order, lo, cnt, out_cap
                    )
                    for pb, bb in zip(p_bits, b_bits):
                        out_live = out_live & K.keys_match(
                            pb, bb, probe_idx, build_idx
                        )
                    if filter_c is not None:
                        pair = {}
                        for s in p_env:
                            pair[s] = K.rows_at(*p_env[s], probe_idx)
                        for s in b_env:
                            pair[s] = K.rows_at(*b_env[s], build_idx)
                        fd, fv = filter_c.fn(pair)
                        out_live = out_live & (
                            fd if fv is None else (fd & fv)
                        )
                    matched = K.range_any(cnt, out_live)
                else:
                    matched = cnt > 0
                return matched

            prog_b = self._shard_jit(fb, "semi_join", in_specs, PS(axis))
            self._mesh_jit_cache[key_b] = prog_b
        matched = self._run(
            prog_b, miss, *p_leaves, *b_leaves, tag="semi-join",
            join_build=filt.capacity, key_bits=key_bits,
            # counted once a semi join: on its count program, where
            # there is one
            join_kind="semi" if needs_expand else kind,
        )
        from trino_tpu import types as T

        names = list(sp.names) + [node.match_symbol]
        cols = list(sp.columns) + [Column(T.BOOLEAN, matched, None, None)]
        return ShardedPage(names, cols, sp.mask, self.n_shards)
