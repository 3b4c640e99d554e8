"""Per-operator profiler: host-side wall-clock attribution joined
with XLA cost analysis.

The reference engine answers "where is this query's time going?" at
operator granularity — OperatorStats hang off every task and roll up
through TaskInfo/StageInfo into the QueryInfo tree
(MAIN/operator/OperatorStats.java). Here the operator is the unit the
executor actually dispatches: a fused FUSABLE chain compiles to ONE
XLA program and therefore profiles as ONE operator (its label names
the whole chain, e.g. ``Filter→Aggregate``); joins, scans and
exchanges profile individually through the same ``execute`` hook.

The TPU-native half: each compiled chain's executable has an XLA cost
model (``compiled.cost_analysis()`` — FLOPs and bytes accessed), so a
record's measured wall time converts into achieved GFLOP/s and an
achieved-vs-roofline utilization. Cost analysis is computed LAZILY per
jit-cache key on first request: the hot dispatch path only stores the
abstract avals; the one extra ``lower().compile()`` resolves through
the persistent XLA cache as a deserialize, not a recompile.

Profiling adds no device syncs: row counts come from ``known_rows``
when the executor already synced (deferred-sync pages report None) and
byte counts come from array shape metadata.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

__all__ = [
    "OperatorProfiler", "OpRecord", "peak_rates", "roofline",
    "attach_roofline", "tree_from_stats", "device_info",
]

#: (peak GFLOP/s, peak GB/s) per ``device_kind`` as jax reports it.
#: A device that is not in the table is an error, not a default: a
#: roofline share against the wrong peaks is worse than none.
_DEVICE_PEAKS = {
    # TPU v5e, one chip: 197 TFLOP/s bf16, 819 GB/s HBM (16 GB) —
    # Google Cloud documentation, "TPU v5e"
    "TPU v5 lite": (197_000.0, 819.0),
    # XLA:CPU, for the tests' virtual devices only — a nominal host,
    # not a measured one; shares against it are not device metrics
    "cpu": (150.0, 50.0),
}


def device_info() -> dict:
    """What ``GET /v1/info`` says about the device: platform,
    device_kind and device_count of the backend this process has
    initialised, and per device the allocator's ``bytes_in_use`` /
    ``peak_bytes_in_use`` (``None`` where the backend keeps no such
    statistics, as XLA:CPU). Where the process has initialised no
    backend everything is ``None``/0/[] — a host-only role must not
    take a chip just to answer."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return {
            "platform": None, "device_kind": None, "device_count": 0,
            "device_memory": [],
        }
    import jax

    devices = jax.devices()
    memory = []
    for d in devices:
        stats = d.memory_stats() or {}
        memory.append({
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "device_memory": memory,
    }


def peak_rates() -> tuple[float, float]:
    """(peak_gflops, peak_gbps) of the default device, for the roofline
    ceiling. TRINO_TPU_PEAK_GFLOPS/_PEAK_GBPS override a known row;
    an unknown ``device_kind`` raises."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in _DEVICE_PEAKS:
        raise LookupError(
            f"no peak rates for device_kind {kind!r}: add a sourced "
            "row to trino_tpu.profiler._DEVICE_PEAKS"
        )
    gflops, gbps = _DEVICE_PEAKS[kind]
    gflops = float(os.environ.get("TRINO_TPU_PEAK_GFLOPS", gflops))
    gbps = float(os.environ.get("TRINO_TPU_PEAK_GBPS", gbps))
    return gflops, gbps


def roofline(flops: float, bytes_accessed: float, wall_ms: float) -> dict:
    """Roofline attribution for one record: achieved GFLOP/s against
    min(compute ceiling, bandwidth ceiling × arithmetic intensity)."""
    if not flops or not wall_ms or wall_ms <= 0:
        return {}
    peak_gflops, peak_gbps = peak_rates()
    achieved = flops / (wall_ms * 1e-3) / 1e9
    out = {"achieved_gflops": round(achieved, 3)}
    if bytes_accessed:
        intensity = flops / bytes_accessed
        ceiling = min(peak_gflops, peak_gbps * intensity)
        out["intensity_flops_per_byte"] = round(intensity, 3)
        out["roofline_gflops"] = round(ceiling, 3)
        if ceiling > 0:
            out["roofline_utilization"] = round(achieved / ceiling, 4)
    return out


@dataclass
class OpRecord:
    op_id: int
    parent_id: int | None
    name: str
    node_type: str
    plan_node_id: int  # id(plan node) — EXPLAIN ANALYZE joins on it
    start_s: float
    wall_ms: float = 0.0
    self_ms: float = 0.0
    rows_out: int | None = None
    bytes_out: int | None = None
    flops: float = 0.0
    bytes_accessed: float = 0.0
    child_ids: list = field(default_factory=list)
    dispatch_keys: list = field(default_factory=list)
    dispatches: int = 0

    def to_dict(self) -> dict:
        d = {
            "op_id": self.op_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "node_type": self.node_type,
            "wall_ms": round(self.wall_ms, 3),
            "self_ms": round(self.self_ms, 3),
            "rows_out": self.rows_out,
            "bytes_out": self.bytes_out,
            "dispatches": self.dispatches,
        }
        if self.flops:
            d["flops"] = self.flops
            d["bytes_accessed"] = self.bytes_accessed
            d.update(roofline(self.flops, self.bytes_accessed, self.self_ms))
        return d


def _page_nbytes(page) -> int | None:
    """Device bytes of a page from shape metadata only (no sync)."""
    try:
        total = 0
        for c in page.columns:
            data = getattr(c, "data", None)
            if data is not None and hasattr(data, "nbytes"):
                total += int(data.nbytes)
            valid = getattr(c, "valid", None)
            if valid is not None and hasattr(valid, "nbytes"):
                total += int(valid.nbytes)
        return total
    except Exception:
        return None


class OperatorProfiler:
    """Stack-based operator timer an executor carries for one query
    (or one fleet task). ``LocalExecutor.execute`` opens a record per
    dispatched operator; recursion through ``self.execute`` nests
    children, so the stack reconstructs the operator tree without the
    profiler knowing anything about plan shapes."""

    def __init__(self):
        self.records: list[OpRecord] = []
        self._stack: list[OpRecord] = []
        self._seq = 0
        self._costs_resolved = False

    # -- executor-facing hooks ------------------------------------------

    def open(self, name: str, node_type: str, plan_node_id: int) -> OpRecord:
        rec = OpRecord(
            op_id=self._seq,
            parent_id=self._stack[-1].op_id if self._stack else None,
            name=name,
            node_type=node_type,
            plan_node_id=plan_node_id,
            start_s=time.perf_counter(),
        )
        self._seq += 1
        if self._stack:
            self._stack[-1].child_ids.append(rec.op_id)
        self.records.append(rec)
        self._stack.append(rec)
        return rec

    def close(self, rec: OpRecord, page=None) -> None:
        rec.wall_ms = (time.perf_counter() - rec.start_s) * 1e3
        while self._stack and self._stack[-1] is not rec:
            self._stack.pop()  # exception unwound through children
        if self._stack:
            self._stack.pop()
        if page is not None:
            known = getattr(page, "known_rows", None)
            if known is not None:
                rec.rows_out = int(known)
            rec.bytes_out = _page_nbytes(page)

    def note_dispatch(self, key) -> None:
        """Called by ``_dispatch_chain`` with the jit-cache key it just
        ran — the handle for lazy XLA cost analysis at finish time."""
        if self._stack:
            top = self._stack[-1]
            top.dispatches += 1
            if key not in top.dispatch_keys:
                top.dispatch_keys.append(key)

    # -- results --------------------------------------------------------

    def finish(self, executor=None) -> list[dict]:
        """Seal records: compute self time (wall minus direct
        children), resolve XLA costs through the executor's lazy
        cost cache, and return JSON-safe operator_stats rows."""
        by_id = {r.op_id: r for r in self.records}
        for rec in self.records:
            child_ms = sum(by_id[c].wall_ms for c in rec.child_ids)
            rec.self_ms = max(rec.wall_ms - child_ms, 0.0)
        if executor is not None and not self._costs_resolved:
            # one-shot: finish() may be called again (timing-only seal
            # then a lazy profile resolve) without double-counting
            self._costs_resolved = True
            for rec in self.records:
                for key in rec.dispatch_keys:
                    cost = executor.chain_cost(key)
                    if cost:
                        rec.flops += cost.get("flops", 0.0)
                        rec.bytes_accessed += cost.get(
                            "bytes_accessed", 0.0
                        )
        return [r.to_dict() for r in self.records]

    def record_for(self, plan_node_id: int) -> OpRecord | None:
        """Latest record for a plan node (EXPLAIN ANALYZE join)."""
        for rec in reversed(self.records):
            if rec.plan_node_id == plan_node_id:
                return rec
        return None


def attach_roofline(stats: list[dict]) -> list[dict]:
    """Fill roofline fields on operator_stats rows that carry raw
    flops/bytes but were serialized before attribution (cross-process
    arrivals where the env-configured peaks differ coordinator-side)."""
    for row in stats:
        if row.get("flops") and "achieved_gflops" not in row:
            row.update(
                roofline(
                    row["flops"],
                    row.get("bytes_accessed", 0.0),
                    row.get("self_ms", 0.0),
                )
            )
    return stats


def tree_from_stats(stats: list[dict]) -> list[dict]:
    """Re-nest a flat operator_stats list (parent_id links) into the
    operator tree used by QueryInfo JSON. Rows arrive JSON-safe from
    workers; the nesting is rebuilt coordinator-side."""
    nodes = {row["op_id"]: dict(row, children=[]) for row in stats}
    roots = []
    for row in stats:
        node = nodes[row["op_id"]]
        parent = row.get("parent_id")
        if parent is not None and parent in nodes:
            nodes[parent]["children"].append(node)
        else:
            roots.append(node)
    return roots
