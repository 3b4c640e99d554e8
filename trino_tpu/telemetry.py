"""Unified telemetry: trace spans, a process-wide metrics registry, and
Prometheus text rendering.

Three cooperating pieces (mirroring the reference engine's airlift stats +
OpenTelemetry tracing split):

* **Spans** — hierarchical wall-clock spans (query → planning → stage →
  task → operator), serialisable so worker-side subtrees can ride back on
  task-status responses and stitch into the coordinator's query trace.
  Exportable as Chrome trace-event JSON (chrome://tracing / Perfetto).
  Every live span is also a ``jax.profiler.TraceAnnotation`` carrying
  its ``query_id``: while a profiler session runs, the span is an event
  on the host plane of the same ``.xplane.pb`` as the device's
  operations — one clock, one tree, two views. With no session running
  the annotation is a flag test.
* **MetricsRegistry** — labelled counters / gauges / histograms rendered
  in Prometheus text exposition format; a process-global ``REGISTRY`` is
  served at ``GET /v1/metrics`` by both coordinator and worker.
* **XLA compile hooks** — a ``jax.monitoring`` duration listener feeding
  compile count/seconds counters, plus ``CountingCache`` wrapping the
  executors' jit caches for hit/miss rates.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

__all__ = [
    "Span",
    "Trace",
    "Tracer",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "CountingCache",
    "REGISTRY",
    "install_jax_compile_hook",
    "render_prometheus",
]


def _now_ms() -> float:
    """Epoch milliseconds — spans from different processes share this clock."""
    return time.time() * 1000.0


#: span ids are 16 hex digits: a prefix drawn once per process, then a
#: count (a uuid4 a span was half a span's cost)
_ID_PREFIX = os.urandom(4).hex()
_ID_COUNT = itertools.count(1)


def _new_id() -> str:
    return f"{_ID_PREFIX}{next(_ID_COUNT) & 0xFFFFFFFF:08x}"


_TraceAnnotation = None


def _annotate(name: str, query_id: str, attrs: Dict[str, Any]):
    """A started ``jax.profiler.TraceAnnotation`` (the timer starts at
    construction). The import is lazy and touches no backend: host-only
    roles open spans too."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name, query_id=query_id, **attrs)


# ---------------------------------------------------------------------------
# Trace spans
# ---------------------------------------------------------------------------


@dataclass
class Span:
    name: str
    kind: str = "internal"  # query|planning|stage|task|operator|spool|rpc|...
    span_id: str = field(default_factory=_new_id)
    parent_id: Optional[str] = None
    trace_id: str = ""
    start_ms: float = field(default_factory=_now_ms)
    duration_ms: float = 0.0
    node: str = ""  # which process produced this span ("" = coordinator)
    attrs: Dict[str, Any] = field(default_factory=dict)
    children: List["Span"] = field(default_factory=list)
    #: the statement this span belongs to (children inherit it)
    query_id: str = ""
    _t0: float = field(default_factory=time.perf_counter, repr=False)
    _open: bool = field(default=True, repr=False)

    def __post_init__(self) -> None:
        # a live span is an event on the profiler's host plane too. The
        # profiler writes the event where the span is finished, so a
        # span closed on another thread than it was opened on (the
        # coordinator's ``statement`` and ``queued``) sits on the
        # closing thread's line, around that thread's children.
        self._ann = (
            _annotate(self.name, self.query_id, self.attrs)
            if self._open else None
        )

    def finish(self) -> "Span":
        if self._open:
            self.duration_ms = (time.perf_counter() - self._t0) * 1000.0
            self._open = False
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
        return self

    def child(self, name: str, kind: str = "internal", **attrs: Any) -> "Span":
        sp = Span(name=name, kind=kind, parent_id=self.span_id,
                  trace_id=self.trace_id, node=self.node, attrs=attrs,
                  query_id=self.query_id)
        self.children.append(sp)
        return sp

    def walk(self) -> Iterator["Span"]:
        yield self
        for c in self.children:
            yield from c.walk()

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "kind": self.kind,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "trace_id": self.trace_id,
            "start_ms": self.start_ms,
            "duration_ms": self.duration_ms,
            "node": self.node,
            "attrs": dict(self.attrs),
            "query_id": self.query_id,
            "children": [c.to_dict() for c in self.children],
        }

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Span":
        sp = Span(
            name=d.get("name", "?"),
            kind=d.get("kind", "internal"),
            span_id=d.get("span_id") or _new_id(),
            parent_id=d.get("parent_id"),
            trace_id=d.get("trace_id", ""),
            start_ms=float(d.get("start_ms", 0.0)),
            duration_ms=float(d.get("duration_ms", 0.0)),
            node=d.get("node", ""),
            attrs=dict(d.get("attrs") or {}),
            query_id=d.get("query_id", ""),
            _open=False,
        )
        sp.children = [Span.from_dict(c) for c in d.get("children") or []]
        return sp


class Trace:
    """A completed span tree for one query, rooted at the query span."""

    def __init__(self, root: Span) -> None:
        self.root = root
        self.trace_id = root.trace_id

    def spans(self) -> List[Span]:
        return list(self.root.walk())

    def find(self, name: Optional[str] = None, kind: Optional[str] = None) -> List[Span]:
        out = []
        for sp in self.root.walk():
            if name is not None and sp.name != name:
                continue
            if kind is not None and sp.kind != kind:
                continue
            out.append(sp)
        return out

    def to_dict(self) -> Dict[str, Any]:
        return self.root.to_dict()

    def to_chrome_json(self) -> str:
        """Render as Chrome trace-event JSON (``ph:"X"`` complete events).

        Loadable in chrome://tracing or https://ui.perfetto.dev. ``pid``
        groups spans by producing node; ``ts``/``dur`` are microseconds.
        """
        events: List[Dict[str, Any]] = []
        pids: Dict[str, int] = {}
        for sp in self.root.walk():
            pid = pids.setdefault(sp.node or "coordinator", len(pids) + 1)
            events.append({
                "name": sp.name,
                "cat": sp.kind,
                "ph": "X",
                "ts": sp.start_ms * 1000.0,
                "dur": max(sp.duration_ms, 0.0) * 1000.0,
                "pid": pid,
                "tid": 1,
                "args": dict(sp.attrs, span_id=sp.span_id,
                             parent_id=sp.parent_id or ""),
            })
        meta = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 1,
             "args": {"name": node}}
            for node, pid in pids.items()
        ]
        return json.dumps({"traceEvents": meta + events,
                           "displayTimeUnit": "ms"}, indent=None)


class Tracer:
    """Builds one query's span tree; cheap enough to always be on.

    The coordinator (or local engine) owns a Tracer per query. Workers
    build detached task subtrees with ``parent_id`` taken from the trace
    context shipped on ``/v1/stagetask`` and return them serialised on the
    task-status response; the coordinator stitches them in with
    :meth:`attach`.
    """

    def __init__(self, query_id: str = "", trace_id: Optional[str] = None,
                 node: str = "", root_name: str = "") -> None:
        self.trace_id = trace_id or _new_id()
        self.node = node
        self.query_id = query_id
        self.root: Optional[Span] = None
        self._stack: List[Span] = []
        if query_id:
            self.root = Span(name=root_name or f"query {query_id}",
                             kind="query", trace_id=self.trace_id,
                             node=node, query_id=query_id)
            self._stack = [self.root]

    # -- span lifecycle ----------------------------------------------------
    def start(self, name: str, kind: str = "internal", parent: Optional[Span] = None,
              **attrs: Any) -> Span:
        """Open a span under ``parent`` (default: top of stack / detached root)."""
        if parent is None and self._stack:
            parent = self._stack[-1]
        if parent is not None:
            sp = parent.child(name, kind, **attrs)
        else:
            sp = Span(name=name, kind=kind, trace_id=self.trace_id,
                      node=self.node, attrs=attrs, query_id=self.query_id)
            if self.root is None:
                self.root = sp
        return sp

    def span(self, name: str, kind: str = "internal", **attrs: Any) -> "_SpanCtx":
        return _SpanCtx(self, name, kind, attrs)

    def attach(self, span_dict: Dict[str, Any]) -> Optional[Span]:
        """Stitch a serialised (worker-side) subtree under its parent span."""
        try:
            sub = Span.from_dict(span_dict)
        except Exception:
            return None
        if self.root is None:
            return None
        parent = None
        if sub.parent_id:
            for sp in self.root.walk():
                if sp.span_id == sub.parent_id:
                    parent = sp
                    break
        (parent or self.root).children.append(sub)
        return sub

    def context(self, parent: Optional[Span] = None) -> Dict[str, str]:
        """Trace-context dict to ship across RPC boundaries."""
        sp = parent or (self._stack[-1] if self._stack else self.root)
        return {"trace_id": self.trace_id,
                "parent_span_id": sp.span_id if sp is not None else ""}

    def finish(self) -> Trace:
        for sp in reversed(self._stack):
            sp.finish()
        if self.root is None:
            self.root = Span(name="query", kind="query", trace_id=self.trace_id,
                             node=self.node)
        self.root.finish()
        return Trace(self.root)


#: per-thread span anchor. Work that has no tracer in reach (the
#: executor's dispatches and host syncs, compile reads that hop to the
#: CompileService thread) records under the span its thread registered
#: here: the engine's ``execute`` span, the worker's task span.
_active = threading.local()


def set_active_span(span: Optional[Span]) -> None:
    """Register the span under which this thread's executor and compile
    work is recorded (``None`` to clear)."""
    _active.span = span


def active_span() -> Optional[Span]:
    """The calling thread's registered span anchor, or ``None``."""
    return getattr(_active, "span", None)


class child_span:
    """``with child_span(name, **attrs):`` — a child of this thread's
    active span for the block, active itself meanwhile so that spans
    opened inside nest under it. It takes its parent's kind (a
    ``dispatch`` under ``execute`` is execution, one under a worker's
    task span is task time), so the flight recorder's buckets read the
    same whether or not the executor's work is split into spans.
    Nothing where no span is active (direct executor use outside a
    statement)."""

    __slots__ = ("_name", "_attrs", "_prev", "span")

    def __init__(self, name: str, **attrs: Any):
        self._name = name
        self._attrs = attrs
        self.span: Optional[Span] = None

    def __enter__(self) -> Optional[Span]:
        parent = getattr(_active, "span", None)
        if parent is not None:
            self._prev = parent
            self.span = _active.span = parent.child(
                self._name, parent.kind, **self._attrs)
        return self.span

    def __exit__(self, *exc: Any) -> None:
        if self.span is not None:
            self.span.finish()
            _active.span = self._prev


#: span names whose occurrences are counted beside their time
_COUNTED = {"host_sync": "host_syncs", "dispatch": "dispatches",
            "split_scan": "resident_split_scans",
            "join_revoked": "revoked_joins"}


#: row field counting the programs of each ``join_search``
_JOIN_SEARCH_FIELDS = {"count": "small_build_joins", "sort": "sorted_joins"}


#: row field counting the programs of a ``join_kind`` other than inner
#: and semi (its series: ``JOIN_KIND_COUNTERS``, by the field's name)
JOIN_KIND_FIELDS = {"left": "outer_joins", "right": "outer_joins",
                    "full": "outer_joins", "anti": "anti_joins"}


def span_totals(root: Span) -> Dict[str, float]:
    """A sealed tree as flat numbers: ``<name>_ms`` summed over the
    spans of each name, a name being the span's first word with ``-``
    as ``_`` (``stage s0`` is ``stage``, ``spool-read`` ``spool_read``),
    and ``host_syncs`` / ``dispatches`` counting those;
    ``direct_groupbys`` / ``streamed_groupbys`` / ``sorted_groupbys``
    count the grouped
    aggregates of the dispatched programs by the path each took (the
    ``dispatch`` span's ``groupbys``) and ``groupby_start_walks`` the
    ``[capacity]``-sized gathers those aggregates read their keys and
    integer sums in at the groups' first rows (the span's
    ``start_walks``); ``compactions`` counts the
    compaction programs and ``compact_gather_ops`` the gather operands
    they were built with (the ``dispatch`` span's ``gather_ops``);
    ``small_build_joins`` / ``sorted_joins`` count the programs that
    hold a ``kernels.join_ranges`` by the search it was built with
    (the ``dispatch`` span's ``join_search``: ``count`` / ``sort``)
    and ``narrow_key_joins`` those among them whose keys were ranked
    below 64 bits, at the width the plan's exact key range needs (the
    span's ``key_bits``), ``wide_key_joins`` the others (a hashed
    multi-column key, a varchar, float or two-limb key, a side without
    exact bounds); ``outer_joins`` / ``anti_joins`` count them by the
    span's ``join_kind`` (``left`` / ``right`` / ``full``; ``anti``: a
    semi join whose match the plan negates), ``distinct_aggregates``
    sums the span's count of DISTINCT aggregate calls in a chain, and
    ``revoked_joins`` counts the ``join-revoked`` spans: joins sent
    through the spill tier because their estimated working set passed
    the per-node memory cap;
    ``mesh_exchanges`` counts the mesh executor's ``mesh-exchange``
    spans and ``mesh_exchanges_in_place`` those among them that were
    satisfied where the rows lay (the span's ``in_place``),
    ``mesh_exchange_live_bytes`` / ``mesh_exchange_buffer_bytes``
    sum their attributes of those names, and ``mesh_upload_ms`` is the
    time of its two host-to-mesh layings-out (``mesh-scan-upload``,
    ``mesh-scatter``); ``resident_split_scans`` counts the
    ``split-scan`` spans: the split scans a worker served as a row
    range of its resident table (one that uploads opens ``upload``
    alone). The root is left out: its time is the statement's
    ``elapsed_ms``."""
    out: Dict[str, float] = {}
    for sp in root.walk():
        if sp is root:
            continue
        key = sp.name.split(" ", 1)[0].replace("-", "_")
        out[key + "_ms"] = out.get(key + "_ms", 0.0) + sp.duration_ms
        if key in _COUNTED:
            out[_COUNTED[key]] = out.get(_COUNTED[key], 0) + 1
        for path in sp.attrs.get("groupbys", ()):
            out[path + "_groupbys"] = out.get(path + "_groupbys", 0) + 1
        if "start_walks" in sp.attrs:
            out["groupby_start_walks"] = out.get(
                "groupby_start_walks", 0) + sp.attrs["start_walks"]
        search = sp.attrs.get("join_search")
        if search is not None:
            field = _JOIN_SEARCH_FIELDS[search]
            out[field] = out.get(field, 0) + 1
            width = ("narrow_key_joins" if sp.attrs.get("key_bits", 64) < 64
                     else "wide_key_joins")
            out[width] = out.get(width, 0) + 1
            kind = JOIN_KIND_FIELDS.get(sp.attrs.get("join_kind"))
            if kind is not None:
                out[kind] = out.get(kind, 0) + 1
        if "distinct_aggregates" in sp.attrs:
            out["distinct_aggregates"] = out.get(
                "distinct_aggregates", 0) + sp.attrs["distinct_aggregates"]
        if sp.attrs.get("program") == "compact":
            out["compactions"] = out.get("compactions", 0) + 1
            out["compact_gather_ops"] = out.get(
                "compact_gather_ops", 0) + sp.attrs.get("gather_ops", 0)
        if key == "mesh_exchange":
            out["mesh_exchanges"] = out.get("mesh_exchanges", 0) + 1
            if sp.attrs.get("in_place"):
                out["mesh_exchanges_in_place"] = out.get(
                    "mesh_exchanges_in_place", 0) + 1
            for attr in ("live_bytes", "buffer_bytes"):
                field = "mesh_exchange_" + attr
                out[field] = out.get(field, 0) + sp.attrs.get(attr, 0)
        elif key in ("mesh_scan_upload", "mesh_scatter"):
            out["mesh_upload_ms"] = (
                out.get("mesh_upload_ms", 0.0) + sp.duration_ms)
    return out


class _SpanCtx:
    def __init__(self, tracer: Tracer, name: str, kind: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name, self._kind, self._attrs = name, kind, attrs
        self.span: Optional[Span] = None

    def __enter__(self) -> Span:
        self.span = self._tracer.start(self._name, self._kind, **self._attrs)
        self._tracer._stack.append(self.span)
        return self.span

    def __exit__(self, *exc: Any) -> None:
        if self.span is not None:
            self.span.finish()
            stack = self._tracer._stack
            if stack and stack[-1] is self.span:
                stack.pop()


# ---------------------------------------------------------------------------
# Metrics registry (Prometheus text exposition)
# ---------------------------------------------------------------------------


def _label_key(labels: Dict[str, str]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _render_labels(key: Tuple[Tuple[str, str], ...]) -> str:
    if not key:
        return ""
    esc = lambda v: v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    return "{" + ",".join(f'{k}="{esc(v)}"' for k, v in key) + "}"


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str) -> None:
        self.name = name
        self.help = help
        self._lock = threading.Lock()

    def render(self) -> List[str]:  # pragma: no cover - overridden
        return []

    def header(self) -> List[str]:
        # exposition format 0.0.4: HELP text escapes backslash+newline
        help_esc = self.help.replace("\\", "\\\\").replace("\n", "\\n")
        return [f"# HELP {self.name} {help_esc}",
                f"# TYPE {self.name} {self.kind}"]


class Counter(_Metric):
    kind = "counter"

    def __init__(self, name: str, help: str) -> None:
        super().__init__(name, help)
        self._values: Dict[Tuple[Tuple[str, str], ...], float] = {}

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels: str) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)

    def total(self) -> float:
        with self._lock:
            return sum(self._values.values())

    def render(self) -> List[str]:
        with self._lock:
            items = sorted(self._values.items())
        if not items:
            items = [((), 0.0)]
        return [f"{self.name}{_render_labels(k)} {_fmt_val(v)}" for k, v in items]


class Gauge(_Metric):
    kind = "gauge"

    def __init__(self, name: str, help: str) -> None:
        super().__init__(name, help)
        self._values: Dict[Tuple[Tuple[str, str], ...], float] = {}
        self._callbacks: List[Callable[[], Dict[Tuple[Tuple[str, str], ...], float]]] = []

    def set(self, value: float, **labels: str) -> None:
        with self._lock:
            self._values[_label_key(labels)] = float(value)

    def add(self, amount: float, **labels: str) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels: str) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)

    def total(self) -> float:
        with self._lock:
            return sum(self._values.values())

    def render(self) -> List[str]:
        with self._lock:
            merged = dict(self._values)
        if not merged:
            merged = {(): 0.0}
        return [f"{self.name}{_render_labels(k)} {_fmt_val(v)}"
                for k, v in sorted(merged.items())]


_DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                    1.0, 2.5, 5.0, 10.0)


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name: str, help: str,
                 buckets: Sequence[float] = _DEFAULT_BUCKETS) -> None:
        super().__init__(name, help)
        self.buckets = tuple(sorted(buckets))
        self._counts: Dict[Tuple[Tuple[str, str], ...], List[int]] = {}
        self._sums: Dict[Tuple[Tuple[str, str], ...], float] = {}
        self._totals: Dict[Tuple[Tuple[str, str], ...], int] = {}

    def observe(self, value: float, **labels: str) -> None:
        key = _label_key(labels)
        with self._lock:
            counts = self._counts.setdefault(key, [0] * len(self.buckets))
            for i, b in enumerate(self.buckets):
                if value <= b:
                    counts[i] += 1
            self._sums[key] = self._sums.get(key, 0.0) + value
            self._totals[key] = self._totals.get(key, 0) + 1

    def count(self, **labels: str) -> int:
        with self._lock:
            return self._totals.get(_label_key(labels), 0)

    def total_count(self) -> int:
        with self._lock:
            return sum(self._totals.values())

    def total_sum(self) -> float:
        with self._lock:
            return sum(self._sums.values())

    def render(self) -> List[str]:
        out: List[str] = []
        with self._lock:
            keys = sorted(self._counts)
            for key in keys:
                counts = self._counts[key]
                for i, b in enumerate(self.buckets):
                    lk = key + (("le", _fmt_val(b)),)
                    out.append(f"{self.name}_bucket{_render_labels(tuple(sorted(lk)))} {counts[i]}")
                lk = key + (("le", "+Inf"),)
                out.append(f"{self.name}_bucket{_render_labels(tuple(sorted(lk)))} {self._totals[key]}")
                out.append(f"{self.name}_sum{_render_labels(key)} {_fmt_val(self._sums[key])}")
                out.append(f"{self.name}_count{_render_labels(key)} {self._totals[key]}")
        return out


def _fmt_val(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class MetricsRegistry:
    """Registry of named metric families; renders Prometheus text format."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    def _get(self, cls: type, name: str, help: str, **kw: Any) -> Any:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help, **kw)
                self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = _DEFAULT_BUCKETS) -> Histogram:
        return self._get(Histogram, name, help, buckets=buckets)

    def render(self) -> str:
        with self._lock:
            metrics = [self._metrics[n] for n in sorted(self._metrics)]
        lines: List[str] = []
        for m in metrics:
            lines.extend(m.header())
            lines.extend(m.render())
        return "\n".join(lines) + "\n"

    def snapshot(self) -> Dict[str, float]:
        """Flat scalar snapshot of every family — counters and gauges
        collapse across label sets; histograms report ``_count`` and
        ``_sum``. The before/after substrate of diagnostic-bundle metric
        deltas and the cluster time-series recorder's self-scrape."""
        with self._lock:
            metrics = [self._metrics[n] for n in sorted(self._metrics)]
        out: Dict[str, float] = {}
        for m in metrics:
            if isinstance(m, Histogram):
                out[f"{m.name}_count"] = float(m.total_count())
                out[f"{m.name}_sum"] = float(m.total_sum())
            elif isinstance(m, (Counter, Gauge)):
                out[m.name] = float(m.total())
        return out


#: Process-global registry served at GET /v1/metrics.
REGISTRY = MetricsRegistry()


def render_prometheus() -> str:
    return REGISTRY.render()


# -- well-known families, created eagerly so /v1/metrics always lists them --

QUERIES_TOTAL = REGISTRY.counter(
    "trino_queries_total", "Completed queries by terminal state")
QUERY_RETRIES = REGISTRY.counter(
    "trino_query_retries_total", "Whole-query re-executions (retry_policy=QUERY)")
TASKS_RETRIED = REGISTRY.counter(
    "trino_tasks_retried_total", "Task attempts re-run after failure")
TASKS_SPECULATED = REGISTRY.counter(
    "trino_tasks_speculated_total", "Speculative duplicate task attempts launched")
SPECULATION_WINS = REGISTRY.counter(
    "trino_speculation_wins_total", "Speculative attempts that finished first")
WORKERS_READMITTED = REGISTRY.counter(
    "trino_workers_readmitted_total", "Workers re-admitted after exclusion")
CHAOS_INJECTIONS = REGISTRY.counter(
    "trino_chaos_injections_total", "Faults fired by the chaos injector, by site")
SPOOL_BYTES_WRITTEN = REGISTRY.counter(
    "trino_spool_bytes_written_total", "Bytes written to exchange spool files")
SPOOL_BYTES_READ = REGISTRY.counter(
    "trino_spool_bytes_read_total", "Bytes read back from exchange spool files")
SPOOL_CRC_FAILURES = REGISTRY.counter(
    "trino_spool_crc_failures_total", "Spool partition reads failing CRC/manifest checks")
EXCHANGE_ROWS = REGISTRY.counter(
    "trino_exchange_rows_total", "Rows moved through mesh exchanges")
EXCHANGE_BYTES = REGISTRY.counter(
    "trino_exchange_bytes_total", "Bytes moved through mesh exchanges")
EXCHANGE_DIRECT_BYTES = REGISTRY.counter(
    "trino_exchange_direct_bytes_total",
    "Exchange bytes served straight from producer memory buffers")
EXCHANGE_SPOOLED_BYTES = REGISTRY.counter(
    "trino_exchange_spooled_bytes_total",
    "Exchange bytes read back from the on-disk spool")
EXCHANGE_BUFFER_RESERVED = REGISTRY.gauge(
    "trino_exchange_buffer_reserved_bytes",
    "Bytes currently held in the worker's direct-exchange buffer pool")
EXCHANGE_BUFFER_EVICTIONS = REGISTRY.counter(
    "trino_exchange_buffer_evictions_total",
    "Direct-exchange buffer entries evicted before every consumer fetched")
MEMORY_RESERVED = REGISTRY.gauge(
    "trino_memory_pool_reserved_bytes", "Currently reserved bytes per memory pool")
MEMORY_PEAK = REGISTRY.gauge(
    "trino_memory_pool_peak_bytes", "High-water reserved bytes per memory pool")
MEMORY_KILLS = REGISTRY.counter(
    "trino_memory_kills_total", "Queries killed by the cluster memory manager")
RPC_LATENCY = REGISTRY.histogram(
    "trino_rpc_latency_seconds", "Coordinator-side fleet RPC latency by op",
    # the poll path lives under 10ms on a local fleet — the default
    # buckets put every sample in the first two and hide the tail
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1.0, 2.5, 10.0))
OPERATOR_SELF_TIME = REGISTRY.histogram(
    "trino_operator_self_time_seconds",
    "Per-operator self time on workers, by operator node type",
    # operators span sub-ms (cached dispatch) to whole-query seconds
    buckets=(0.0005, 0.002, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
             5.0, 15.0, 60.0))
XLA_COMPILES = REGISTRY.counter(
    "trino_xla_compile_total", "XLA backend compilations observed via jax.monitoring")
XLA_COMPILE_SECONDS = REGISTRY.counter(
    "trino_xla_compile_seconds_total", "Cumulative XLA backend compile seconds")
JIT_CACHE_HITS = REGISTRY.counter(
    "trino_jit_cache_hits_total", "Executor jit-cache hits, by cache")
JIT_CACHE_MISSES = REGISTRY.counter(
    "trino_jit_cache_misses_total", "Executor jit-cache misses, by cache")
STREAMED_GROUPBY_FALLBACKS = REGISTRY.counter(
    "trino_streamed_groupby_fallbacks_total",
    "Chains rerun by sort after a declared row order failed its device check")
JOINS = REGISTRY.counter(
    "trino_joins_total",
    "Dispatched programs holding a kernels.join_ranges, by the search it was "
    "built with: count (a small build) or sort, and by the width its keys "
    "were ranked at (key_bits: 64, or what the plan's exact key range needs)")
WIDE_KEY_JOINS = REGISTRY.counter(
    "trino_wide_key_joins_total",
    "Dispatched programs holding a kernels.join_ranges whose keys were ranked "
    "at 64 bits: a hashed multi-column key, a varchar, float or two-limb key, "
    "or a side without exact bounds")
OUTER_JOINS = REGISTRY.counter(
    "trino_outer_joins_total",
    "Dispatched join programs of kind left, right or full")
ANTI_JOINS = REGISTRY.counter(
    "trino_anti_joins_total",
    "Dispatched semi-join programs whose match the plan negates "
    "(NOT IN / NOT EXISTS)")
JOIN_KIND_COUNTERS = {"outer_joins": OUTER_JOINS, "anti_joins": ANTI_JOINS}
DISTINCT_AGGREGATES = REGISTRY.counter(
    "trino_distinct_aggregates_total",
    "DISTINCT aggregate calls in dispatched chain programs")
JOIN_REVOCATIONS = REGISTRY.counter(
    "trino_join_revocations_total",
    "Joins sent through the spill tier because their estimated working set "
    "passed query_max_memory_per_node (span join-revoked)")
LISTENER_FAILURES = REGISTRY.counter(
    "trino_event_listener_failures_total", "EventListener callbacks that raised")
WORKER_TASKS = REGISTRY.counter(
    "trino_worker_tasks_total", "Stage tasks executed by this worker, by state")
SCHED_ADMISSIONS = REGISTRY.counter(
    "trino_sched_admissions_total", "Fleet stage tasks admitted, by stage_admission mode")
SCHED_ADMISSION_WAIT = REGISTRY.histogram(
    "trino_sched_admission_wait_seconds", "Queue-to-first-dispatch wait per fleet task, by mode")
SCHED_OVERLAP = REGISTRY.gauge(
    "trino_sched_overlap_seconds", "Producer/consumer overlap won by pipelined admission, last fleet query")
SCHED_RESCINDS = REGISTRY.counter(
    "trino_sched_rescinds_total", "Pipelined admissions rescinded after a producer-attempt quarantine")
SHAPE_PAD_WASTE = REGISTRY.gauge(
    "trino_shape_bucket_pad_waste_ratio",
    "Fraction of bucketed capacity lost to padding, by bucketing site")
PERSISTENT_CACHE_DEGRADED = REGISTRY.gauge(
    "trino_persistent_cache_degraded",
    "1 when this process fell back to in-memory-only compilation after a wedged cache deserialize")
COMPILE_DESERIALIZE_FALLBACKS = REGISTRY.counter(
    "trino_compile_deserialize_fallbacks_total",
    "Compile-service watchdog trips: cache-backed compilations abandoned past the deadline")
PERSISTENT_CACHE_HITS = REGISTRY.counter(
    "trino_persistent_cache_hits_total",
    "XLA programs deserialized from the on-disk compilation cache instead of compiled")
DISPATCH_QUEUE_DEPTH = REGISTRY.gauge(
    "trino_dispatch_queue_depth",
    "Fleet slot requests waiting in the fair-share dispatch queue, by resource group")
SLOT_WAIT = REGISTRY.histogram(
    "trino_slot_wait_seconds",
    "Wait from slot request to fleet-slot grant under fair-share dispatch",
    # slot waits range from instant (idle fleet) to whole-query
    # runtimes under saturation — match the sched-admission spread
    buckets=(0.0005, 0.002, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
             5.0, 15.0, 60.0))
QUERIES_RUNNING = REGISTRY.gauge(
    "trino_queries_running",
    "Queries currently holding a running slot, by resource group")
QUERIES_QUEUED = REGISTRY.gauge(
    "trino_queries_queued",
    "Queries waiting in admission queues, by resource group")
SCAN_CACHE_HITS = REGISTRY.counter(
    "trino_scan_cache_hits_total",
    "Table-scan page materializations served from the shared scan-page cache")
SCAN_CACHE_MISSES = REGISTRY.counter(
    "trino_scan_cache_misses_total",
    "Table-scan page materializations that had to hit the connector")
SCAN_CACHE_RESIDENT_BYTES = REGISTRY.gauge(
    "trino_scan_cache_resident_bytes",
    "Device bytes of the whole-table pages the shared scan-page cache holds (data, validity, live masks)")
SCAN_CACHE_RESIDENT_TABLES = REGISTRY.gauge(
    "trino_scan_cache_resident_tables",
    "Tables with a whole-table page resident in the shared scan-page cache")
RESIDENT_SPLIT_SCANS = REGISTRY.counter(
    "trino_resident_split_scans_total",
    "Split scans served as a row range of the resident whole-table page, by table")
RESULT_CACHE_HITS = REGISTRY.counter(
    "trino_result_cache_hits_total",
    "Statements served byte-identical from a semantic result cache")
RESULT_CACHE_MISSES = REGISTRY.counter(
    "trino_result_cache_misses_total",
    "Result-cache probes that fell through to execution")
RESULT_CACHE_BYTES = REGISTRY.gauge(
    "trino_result_cache_bytes",
    "Host bytes resident in semantic result caches")
DEVICE_CACHE_ENTRIES = REGISTRY.gauge(
    "trino_device_cache_entries",
    "Pages pinned in the HBM-resident device table cache")
DEVICE_CACHE_BYTES = REGISTRY.gauge(
    "trino_device_cache_bytes",
    "Device bytes pinned by the HBM-resident table cache")
DEVICE_CACHE_EVICTIONS = REGISTRY.counter(
    "trino_device_cache_evictions_total",
    "Device-cache entries evicted (LRU pressure or pool revocation)")
SCAN_ROWGROUPS_TOTAL = REGISTRY.counter(
    "trino_scan_rowgroups_total",
    "Storage row groups considered by split generation / pruned scans")
SCAN_ROWGROUPS_PRUNED = REGISTRY.counter(
    "trino_scan_rowgroups_pruned",
    "Row groups skipped by min/max footer-stat pruning")
SCAN_PARTITIONS_PRUNED = REGISTRY.counter(
    "trino_scan_partitions_pruned",
    "Hive-style partition directories skipped by partition-value pruning")
SCAN_BYTES_READ = REGISTRY.counter(
    "trino_scan_bytes_read",
    "Compressed storage bytes actually read from columnar files")
SCAN_BATCHES = REGISTRY.counter(
    "trino_scan_batches",
    "Row-group batches streamed through the out-of-core scan operator")
EXCHANGE_PARTITION_ROWS = REGISTRY.counter(
    "trino_exchange_partition_rows",
    "Rows routed to each output partition across exchange edges "
    "(spool boundary always; mesh all_to_all exactly when the "
    "exchange_partition_counters debug sync is on, or every Nth "
    "exchange under exchange_partition_counter_sample)")
EXCHANGE_PARTITION_BYTES = REGISTRY.counter(
    "trino_exchange_partition_bytes",
    "Encoded bytes routed to each output partition at the spool "
    "exchange boundary")
EXCHANGE_SALTED_ROWS = REGISTRY.counter(
    "trino_exchange_salted_rows_total",
    "Rows read through SALTED exchange edges (hot partitions fanned "
    "out across salt tasks), labelled fanout vs replicate")
ADAPTIVE_REPARTITIONS = REGISTRY.counter(
    "trino_adaptive_repartitions_total",
    "Stages whose output partition count was grown at runtime after "
    "an input edge blew past its cardinality estimate")
DIAG_BUNDLES = REGISTRY.counter(
    "trino_diag_bundles_total",
    "Post-mortem diagnostic bundles assembled, by trigger error class")
TIMESERIES_SAMPLES = REGISTRY.counter(
    "trino_timeseries_samples_total",
    "Cluster time-series scrape rounds recorded into the ring")
TIMESERIES_SCRAPE_FAILURES = REGISTRY.counter(
    "trino_timeseries_scrape_failures_total",
    "Worker /v1/metrics scrapes that failed during a time-series round")
PROGRAM_CATALOG_ENTRIES = REGISTRY.gauge(
    "trino_program_catalog_entries",
    "Compiled XLA programs currently retained in the program catalog")
PROGRAM_REGISTRATIONS = REGISTRY.counter(
    "trino_program_catalog_registrations_total",
    "Compiled programs registered in the catalog, by registering source")
PROGRAM_EVICTIONS = REGISTRY.counter(
    "trino_program_catalog_evictions_total",
    "Program-catalog entries evicted past the retention cap (LRU)")
MEMORY_ESTIMATE_RATIO = REGISTRY.gauge(
    "trino_memory_estimate_ratio",
    "memory_analysis() temp+output bytes over the MemoryContext "
    "reservation for the same query — the estimate-based governor's "
    "error, last measured query")
KERNEL_PROFILES = REGISTRY.counter(
    "trino_kernel_profiles_total",
    "Device profile captures taken by the kernel observatory, by trigger")
CLUSTER_WORKERS = REGISTRY.gauge(
    "trino_cluster_workers",
    "Workers currently registered with the membership layer, by "
    "lifecycle state (active / draining / inactive)")
DRAIN_DURATION = REGISTRY.histogram(
    "trino_drain_duration_seconds",
    "Graceful-drain wall time: POST /v1/drain to deregistration "
    "(running tasks finished AND every dependent consumer committed)",
    buckets=(0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0))
MEMBERSHIP_TRANSITIONS = REGISTRY.counter(
    "trino_membership_transitions_total",
    "Membership state-machine transitions, labelled from/to")
ORPHAN_TASKS_REAPED = REGISTRY.counter(
    "trino_orphan_tasks_reaped_total",
    "Worker tasks cancelled by the orphan reaper after their "
    "coordinator went silent past the liveness TTL")
EXCHANGE_BUFFER_ORPHAN_EVICTIONS = REGISTRY.counter(
    "trino_exchange_buffer_orphan_evictions_total",
    "Exchange-buffer entries released by the orphan reaper for "
    "queries whose coordinator stopped polling (memory that a dead "
    "coordinator would otherwise pin forever)")
JOURNAL_APPENDS = REGISTRY.counter(
    "trino_journal_appends_total",
    "Query-journal WAL records fsync'd, by record type")
QUERIES_RECOVERED = REGISTRY.counter(
    "trino_queries_recovered_total",
    "Journaled queries adopted by a restarted coordinator, by outcome "
    "(resumed / rehydrated / unresumable)")
JOURNAL_GC_REMOVED = REGISTRY.counter(
    "trino_journal_gc_removed_total",
    "Terminal query-journal entries removed by the tracker's periodic "
    "GC sweep (keeps _journal/ bounded across restarts)")
HISTORY_ENTRIES = REGISTRY.gauge(
    "trino_history_entries",
    "Completed-query records currently retained by the performance "
    "sentry's history store")
ANOMALIES = REGISTRY.counter(
    "trino_anomalies_total",
    "Completion-time anomaly verdicts emitted by the performance "
    "sentry, by driver bucket (xla_compile / scan / exchange / "
    "straggler_slack / cache_miss_expected_hit / ...)")
WRITE_ROWS = REGISTRY.counter(
    "trino_write_rows_total",
    "Rows appended through TableWriter sinks (counted at the writing "
    "task, before commit)")
WRITE_BYTES = REGISTRY.counter(
    "trino_write_bytes_total",
    "Bytes written by TableWriter sinks into staged / committed "
    "storage artifacts")
WRITE_FILES = REGISTRY.counter(
    "trino_write_files_total",
    "Storage files sealed by TableWriter sinks (parquet part files; "
    "memory fragments count as one each)")
WRITE_COMMIT_SECONDS = REGISTRY.histogram(
    "trino_write_commit_seconds",
    "TableFinish commit latency: Connector.finish_write wall time "
    "(CRC verify + atomic renames + manifest publish)",
    buckets=(0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0))
PROCESS_RSS = REGISTRY.gauge(
    "trino_process_rss_bytes",
    "Resident set size of this node process")
PROCESS_OPEN_FDS = REGISTRY.gauge(
    "trino_process_open_fds",
    "Open file descriptors held by this node process")
PROCESS_THREADS = REGISTRY.gauge(
    "trino_process_threads",
    "Live Python threads in this node process")
PROCESS_UPTIME = REGISTRY.gauge(
    "trino_process_uptime_seconds",
    "Seconds since this node process imported the engine")
BUILD_INFO = REGISTRY.gauge(
    "trino_build_info",
    "Constant 1, labelled with the engine version and node role "
    "(info-style gauge)")

#: module-import timestamp — the uptime gauge's epoch
_PROCESS_START = time.time()


def _read_rss_bytes() -> int:
    """RSS from /proc (Linux); getrusage fallback elsewhere."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(rss_kb) * 1024
    except Exception:
        return 0


def refresh_process_gauges(node: str = "unknown") -> None:
    """Refresh the process-health gauge family (called by both node
    types' ``/v1/metrics`` handlers just before rendering, so scrapes
    always see current values without any background thread)."""
    PROCESS_RSS.set(_read_rss_bytes())
    try:
        PROCESS_OPEN_FDS.set(len(os.listdir("/proc/self/fd")))
    except OSError:
        pass
    PROCESS_THREADS.set(threading.active_count())
    PROCESS_UPTIME.set(time.time() - _PROCESS_START)
    try:
        from trino_tpu import __version__ as _version
    except Exception:
        _version = "unknown"
    BUILD_INFO.set(1, version=_version, node=node)


# ---------------------------------------------------------------------------
# XLA compile instrumentation
# ---------------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_PCACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_hook_installed = False
_hook_lock = threading.Lock()
#: per-thread flag: a persistent-cache hit event precedes the
#: backend_compile_duration event of the SAME compile request (which,
#: on this jax version, fires for retrievals too — counting it as a
#: compile would make warm processes look cold)
_hook_tls = threading.local()


def install_jax_compile_hook() -> bool:
    """Register jax.monitoring listeners feeding the compile counters.

    ``trino_xla_compile_total`` counts REAL backend compiles only:
    ``backend_compile_duration`` fires for persistent-cache retrievals
    as well, so a preceding ``cache_hits`` event (same thread, same
    request) reroutes that sample to
    ``trino_persistent_cache_hits_total`` instead.

    Idempotent; returns True. Uses the private ``jax._src.monitoring``
    registration API of the installed jax (0.9.0): were it missing the
    import raises — compile counters that silently read 0 would make
    every process look warm.
    """
    global _hook_installed
    with _hook_lock:
        if _hook_installed:
            return True
        from jax._src import monitoring as _mon

        def _on_event(event: str, **kw: Any) -> None:
            if event == _PCACHE_HIT_EVENT:
                _hook_tls.pcache_hit = True

        def _on_duration(event: str, duration: float, **kw: Any) -> None:
            if event == _COMPILE_EVENT:
                if getattr(_hook_tls, "pcache_hit", False):
                    _hook_tls.pcache_hit = False
                    PERSISTENT_CACHE_HITS.inc()
                else:
                    XLA_COMPILES.inc()
                    XLA_COMPILE_SECONDS.inc(duration)

        _mon.register_event_listener(_on_event)
        _mon.register_event_duration_secs_listener(_on_duration)
        _hook_installed = True
        return True


def compile_snapshot() -> Dict[str, float]:
    """Current compile/cache counter values (for before/after deltas)."""
    return {
        "compiles": XLA_COMPILES.total(),
        "compile_seconds": XLA_COMPILE_SECONDS.total(),
        "cache_hits": JIT_CACHE_HITS.total(),
        "cache_misses": JIT_CACHE_MISSES.total(),
        "persistent_hits": PERSISTENT_CACHE_HITS.total(),
    }


class CountingCache(dict):
    """A jit cache dict that counts hit/miss rates into the registry.

    Drop-in for the executors' ``self._jit_cache`` dicts: ``.get`` misses
    and ``__contains__`` checks that come up empty count as misses; the
    matching ``.get``/``[]`` that find an entry count as hits.
    """

    _MISS = object()

    def __init__(self, cache_name: str) -> None:
        super().__init__()
        self._cache_name = cache_name

    def get(self, key: Any, default: Any = None) -> Any:
        hit = dict.get(self, key, CountingCache._MISS)
        if hit is CountingCache._MISS:
            JIT_CACHE_MISSES.inc(cache=self._cache_name)
            return default
        JIT_CACHE_HITS.inc(cache=self._cache_name)
        return hit
