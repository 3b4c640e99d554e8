"""Logical plan nodes.

The analog of the reference's PlanNode hierarchy
(MAIN/sql/planner/plan/, ~60 node types). Symbols are plain strings
with a type map carried per node (the reference's Symbol + TypeProvider
split). Kept deliberately small; nodes are added as engine features
land, mirroring: TableScanNode, FilterNode, ProjectNode,
AggregationNode, JoinNode, SemiJoinNode, SortNode, TopNNode, LimitNode,
OutputNode, ValuesNode, ExchangeNode.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from trino_tpu import types as T
from trino_tpu.expr.ir import AggCall, Call, InputRef, RowExpression

__all__ = [
    "PlanNode", "TableScan", "Filter", "Project", "Aggregate", "Join",
    "SemiJoin", "Sort", "TopN", "Limit", "Output", "Values", "Exchange",
    "SortKey", "Window", "WindowCall", "Union", "Unnest", "RemoteSource",
    "GroupId", "TableWriter", "TableFinish",
]


@dataclass
class PlanNode:
    #: output symbol name -> type, in column order
    outputs: dict[str, T.DataType]

    @property
    def sources(self) -> list["PlanNode"]:
        return []


@dataclass
class TableScan(PlanNode):
    catalog: str = ""
    schema: str = ""
    table: str = ""
    #: output symbol -> connector column name
    assignments: dict[str, str] = field(default_factory=dict)
    #: symbols to scan as hash-coded varchar (plan.stats.annotate:
    #: high-NDV columns used only in equality/grouping/count contexts —
    #: skips the sorted-dictionary build)
    hash_varchar: list[str] | None = None
    #: optional (start_row, row_count) split assigned to this scan —
    #: the unit of source parallelism in fleet mode (the analog of a
    #: ConnectorSplit riding a task RPC, SPI/connector/ConnectorSplit.java)
    split: tuple[int, int] | None = None
    #: TupleDomain-lite pushdown: connector column name ->
    #: (lo, hi, lo_strict, hi_strict) storage-domain interval derived
    #: from the filter above the scan (plan.optimizer); connectors with
    #: ``supports_domains`` prune storage units by footer stats — the
    #: filter always re-applies, so pruning is advisory-safe
    domains: dict | None = None


@dataclass
class RemoteSource(PlanNode):
    """Leaf standing for the output of an upstream stage, read from the
    spooled exchange (the analog of the reference's RemoteSourceNode,
    MAIN/sql/planner/plan/RemoteSourceNode.java: an ExchangeOperator
    pulling pages produced by another stage's tasks). The executor is
    handed the pages out-of-band (task inputs resolved from spool)."""

    source_id: str = ""


@dataclass
class Filter(PlanNode):
    source: PlanNode = None  # type: ignore[assignment]
    predicate: RowExpression = None  # type: ignore[assignment]

    @property
    def sources(self):
        return [self.source]


@dataclass
class Project(PlanNode):
    source: PlanNode = None  # type: ignore[assignment]
    #: output symbol -> expression over source symbols
    assignments: dict[str, RowExpression] = field(default_factory=dict)

    @property
    def sources(self):
        return [self.source]


@dataclass
class Aggregate(PlanNode):
    source: PlanNode = None  # type: ignore[assignment]
    group_keys: list[str] = field(default_factory=list)
    #: output symbol -> aggregate call (args are symbols of source)
    aggregates: dict[str, AggCall] = field(default_factory=dict)
    #: PARTIAL | FINAL | SINGLE — set by the optimizer when splitting
    step: str = "SINGLE"
    #: stats annotations (plan.stats.annotate): expected distinct group
    #: count, and EXACT (lo, hi) value bounds per integer group key for
    #: value-range key packing
    est_groups: float | None = None
    key_ranges: dict[str, tuple[int, int]] | None = None

    @property
    def sources(self):
        return [self.source]


@dataclass
class Join(PlanNode):
    kind: str = "inner"  # inner/left/right/full/cross
    left: PlanNode = None  # type: ignore[assignment]
    right: PlanNode = None  # type: ignore[assignment]
    #: equi-join clauses: (left symbol, right symbol)
    criteria: list[tuple[str, str]] = field(default_factory=list)
    #: residual non-equi condition evaluated on joined rows
    filter: RowExpression | None = None
    #: join distribution chosen by the optimizer: PARTITIONED|BROADCAST
    distribution: str | None = None
    #: dynamic-filtering hints (plan.stats.annotate): expected probe
    #: keep fraction under a build min/max range filter
    #: (df_range_keep) and under exact build-key membership
    #: (df_keep_frac); None = unknown, executors skip the filter
    df_range_keep: float | None = None
    df_keep_frac: float | None = None
    #: stats annotation (plan.stats.annotate): for an equi criterion
    #: whose two symbols both carry EXACT integer bounds, one (lo, hi)
    #: that holds every live, non-NULL key of either INPUT — the
    #: executor shifts the key to lo and ranks it at bit_length(hi - lo)
    #: bits (kernels.join_ranges' key_bits). A criterion without one
    #: is ranked at 64
    key_ranges: dict[tuple[str, str], tuple[int, int]] | None = None

    @property
    def sources(self):
        return [self.left, self.right]


@dataclass
class SemiJoin(PlanNode):
    """Produces source rows + a boolean membership symbol
    (MAIN/sql/planner/plan/SemiJoinNode.java analog)."""

    source: PlanNode = None  # type: ignore[assignment]
    filter_source: PlanNode = None  # type: ignore[assignment]
    #: (source symbol, filter-source symbol) equi pairs
    keys: list[tuple[str, str]] = field(default_factory=list)
    match_symbol: str = ""
    #: residual predicate over (source row, filter-source row) pairs —
    #: correlated non-equi conjuncts from EXISTS subqueries (the
    #: reference plans these as correlated-join filters)
    filter: RowExpression | None = None
    #: True for IN-subquery semantics (3-valued NULL handling); False
    #: for EXISTS, which is always TRUE/FALSE (reference distinguishes
    #: these via SemiJoinNode vs CorrelatedJoin rewrites)
    null_aware: bool = False
    #: as ``Join.key_ranges``, by (source symbol, filter-source symbol)
    key_ranges: dict[tuple[str, str], tuple[int, int]] | None = None

    @property
    def sources(self):
        return [self.source, self.filter_source]

    def negated_by(self, above: PlanNode) -> bool:
        """Whether ``above``, the node this one runs under, is a Filter
        with ``not(<match symbol>)`` among its conjuncts: the plan of
        NOT IN / NOT EXISTS, an anti join."""
        if not isinstance(above, Filter):
            return False
        todo = [above.predicate]
        while todo:
            e = todo.pop()
            if isinstance(e, Call) and e.name == "and":
                todo.extend(e.args)
            elif (
                isinstance(e, Call) and e.name == "not"
                and isinstance(e.args[0], InputRef)
                and e.args[0].name == self.match_symbol
            ):
                return True
        return False


@dataclass
class SortKey:
    symbol: str
    ascending: bool = True
    nulls_first: bool | None = None


@dataclass
class WindowCall:
    """One window function over the node's shared window specification
    (MAIN/sql/planner/plan/WindowNode.Function analog)."""

    name: str  # row_number/rank/dense_rank/ntile/lead/lag/first_value/
    #          last_value/sum/avg/count/count_all/min/max
    args: tuple[RowExpression, ...]
    type: T.DataType
    #: (mode, start, end) with bounds ("unbounded_preceding"|"preceding"
    #: |"current"|"following"|"unbounded_following", offset|None);
    #: None = the SQL default frame (RANGE UNBOUNDED PRECEDING..CURRENT)
    frame: tuple | None = None

    def __repr__(self):
        return f"{self.name}({', '.join(map(repr, self.args))})"


@dataclass
class Window(PlanNode):
    """Adds window-function columns; row-preserving
    (MAIN/operator/WindowOperator.java analog). All functions of one
    node share the same PARTITION BY / ORDER BY."""

    source: PlanNode = None  # type: ignore[assignment]
    partition_by: list[str] = field(default_factory=list)
    order_keys: list[SortKey] = field(default_factory=list)
    #: output symbol -> window call (args are symbols of source)
    functions: dict[str, WindowCall] = field(default_factory=dict)

    @property
    def sources(self):
        return [self.source]


@dataclass
class Unnest(PlanNode):
    """Expand ARRAY constructors into rows (UnnestOperator analog,
    MAIN/operator/unnest/UnnestOperator.java). Each entry of ``arrays``
    is one ARRAY[...] argument's element expressions (over source
    symbols); multiple arrays zip, shorter ones NULL-pad (Trino
    semantics). The fan-out is static (len of the longest array), so
    the expansion is one fixed-shape reshape — the TPU-native form."""

    source: PlanNode = None  # type: ignore[assignment]
    arrays: list[tuple] = field(default_factory=list)
    element_symbols: list[str] = field(default_factory=list)

    @property
    def sources(self):
        return [self.source]


@dataclass
class GroupId(PlanNode):
    """Replicates the input once per grouping set with a set-id column;
    key columns not in a copy's set are NULLed (the
    MAIN/sql/planner/plan/GroupIdNode.java /
    MAIN/operator/GroupIdOperator.java analog). In the batch model the
    replication is one device concat of k masked copies — the
    aggregation above groups on (id, all keys), so rows of different
    sets can never collide even when a NULLed key meets a real NULL."""

    source: PlanNode = None  # type: ignore[assignment]
    #: one list of key symbols per grouping set
    grouping_sets: list[list[str]] = field(default_factory=list)
    id_symbol: str = "$groupid"

    @property
    def sources(self):
        return [self.source]


@dataclass
class Union(PlanNode):
    """UNION ALL: concatenation of sources
    (MAIN/sql/planner/plan/UnionNode.java analog). Distinct set
    semantics are planned as an Aggregate above, INTERSECT/EXCEPT as a
    marker column + group filter."""

    all_sources: list[PlanNode] = field(default_factory=list)
    #: output symbol -> per-source input symbols (one per source)
    symbol_map: dict[str, list[str]] = field(default_factory=dict)

    @property
    def sources(self):
        return list(self.all_sources)


@dataclass
class Sort(PlanNode):
    source: PlanNode = None  # type: ignore[assignment]
    keys: list[SortKey] = field(default_factory=list)

    @property
    def sources(self):
        return [self.source]


@dataclass
class TopN(PlanNode):
    source: PlanNode = None  # type: ignore[assignment]
    count: int = 0
    keys: list[SortKey] = field(default_factory=list)

    @property
    def sources(self):
        return [self.source]


@dataclass
class Limit(PlanNode):
    source: PlanNode = None  # type: ignore[assignment]
    count: int = 0
    offset: int = 0

    @property
    def sources(self):
        return [self.source]


@dataclass
class Values(PlanNode):
    rows: list[list] = field(default_factory=list)


@dataclass
class Exchange(PlanNode):
    """Repartitioning boundary inserted by the optimizer
    (MAIN/sql/planner/plan/ExchangeNode.java analog). scope=REMOTE
    becomes an ICI all_to_all / all_gather; scope=LOCAL a host-side
    reshard."""

    source: PlanNode = None  # type: ignore[assignment]
    partitioning: str = "single"  # single | hash | broadcast | range | source
    hash_symbols: list[str] = field(default_factory=list)
    scope: str = "REMOTE"
    #: whether the source subtree executes distributed ("dist") or as a
    #: single local page ("single") — set by plan.distribute
    input_dist: str = "dist"
    #: range partitioning (distributed ORDER BY): rows route to shards
    #: by sampled splitters of the FIRST sort key, so per-shard sorts
    #: concatenate into global order (the merge-exchange analog,
    #: MAIN/operator/MergeOperator.java / MergeSortedPages.java)
    sort_keys: list["SortKey"] | None = None
    #: single-gather of range-sorted shards: concatenation preserves
    #: the global order (no coordinator re-sort)
    ordered: bool = False

    @property
    def sources(self):
        return [self.source]


@dataclass
class Output(PlanNode):
    source: PlanNode = None  # type: ignore[assignment]
    #: user-facing column names in order
    names: list[str] = field(default_factory=list)
    symbols: list[str] = field(default_factory=list)

    @property
    def sources(self):
        return [self.source]


@dataclass
class TableWriter(PlanNode):
    """Drains its source into a connector WriteSink
    (MAIN/sql/planner/plan/TableWriterNode.java /
    MAIN/operator/TableWriterOperator.java analog). Emits one row per
    sealed fragment: ($rows, $bytes, $fragment) — the fragment strings
    ride the exchange fabric up to TableFinish, so a distributed write
    is just another stage whose (tiny) output spools with first-commit-
    wins attempt dedup, giving exactly-once fragment selection for
    free."""

    source: PlanNode = None  # type: ignore[assignment]
    #: JSON-safe connector write handle: {catalog, schema, table, mode,
    #: columns: [[name, type_str], ...], partition_by, ...} produced by
    #: Connector.begin_insert/begin_create (side-effect free)
    handle: dict = field(default_factory=dict)
    #: source symbols in target-table column order (position i feeds
    #: handle["columns"][i])
    columns: list[str] = field(default_factory=list)

    @property
    def sources(self):
        return [self.source]


@dataclass
class TableFinish(PlanNode):
    """Single-task commit stage above the writers
    (MAIN/sql/planner/plan/TableFinishNode.java /
    MAIN/operator/TableFinishOperator.java analog): gathers the winning
    attempts' fragment rows and calls Connector.finish_write exactly
    once. Output: a single-row ($written) count."""

    source: PlanNode = None  # type: ignore[assignment]
    handle: dict = field(default_factory=dict)

    @property
    def sources(self):
        return [self.source]


def plan_tree_str(node: PlanNode, indent: int = 0) -> str:
    """EXPLAIN-style rendering (MAIN/sql/planner/planprinter analog)."""
    pad = "  " * indent
    name = type(node).__name__
    detail = ""
    if isinstance(node, TableScan):
        detail = f"[{node.catalog}.{node.schema}.{node.table}]"
    elif isinstance(node, Filter):
        detail = f"[{node.predicate!r}]"
    elif isinstance(node, Project):
        detail = "[" + ", ".join(f"{k} := {v!r}" for k, v in node.assignments.items()) + "]"
    elif isinstance(node, Aggregate):
        detail = f"[{node.step} keys={node.group_keys} aggs=" + \
            ", ".join(f"{k}:={v!r}" for k, v in node.aggregates.items()) + "]"
    elif isinstance(node, Join):
        detail = f"[{node.kind} {node.criteria}" + (
            f" filter={node.filter!r}" if node.filter else "") + "]"
    elif isinstance(node, SemiJoin):
        detail = f"[{node.keys} -> {node.match_symbol}]"
    elif isinstance(node, (Sort, TopN)):
        ks = ", ".join(f"{k.symbol} {'asc' if k.ascending else 'desc'}" for k in node.keys)
        n = f" n={node.count}" if isinstance(node, TopN) else ""
        detail = f"[{ks}{n}]"
    elif isinstance(node, Limit):
        detail = f"[{node.count}]"
    elif isinstance(node, Window):
        ks = ", ".join(
            f"{k.symbol} {'asc' if k.ascending else 'desc'}"
            for k in node.order_keys
        )
        detail = (
            f"[partition={node.partition_by} order=[{ks}] fns="
            + ", ".join(f"{k}:={v!r}" for k, v in node.functions.items())
            + "]"
        )
    elif isinstance(node, Union):
        detail = f"[{len(node.all_sources)} branches]"
    elif isinstance(node, GroupId):
        detail = f"[{node.grouping_sets} -> {node.id_symbol}]"
    elif isinstance(node, Exchange):
        detail = f"[{node.scope} {node.partitioning} {node.hash_symbols}]"
    elif isinstance(node, Output):
        detail = f"[{node.names}]"
    elif isinstance(node, (TableWriter, TableFinish)):
        h = node.handle
        pb = h.get("partition_by") or []
        detail = (
            f"[{h.get('catalog', '')}.{h.get('schema', '')}."
            f"{h.get('table', '')} {h.get('mode', '')}"
            + (f" partition_by={pb}" if pb else "") + "]"
        )
    lines = [f"{pad}{name}{detail} -> {list(node.outputs)}"]
    for s in node.sources:
        lines.append(plan_tree_str(s, indent + 1))
    return "\n".join(lines)
