"""Plan/expression JSON codec — the host-boundary serialization layer.

The analog of the reference's plan-fragment wire format (Jackson JSON
of PlanFragment + RowExpressions shipped in POST /v1/task bodies,
MAIN/server/TaskResource.java:135): inside a mesh nothing serializes
(device arrays ride collectives), but across PROCESS boundaries — the
coordinator/worker seam standing in for DCN — plans travel as plain
JSON. Deliberately not pickle: the wire format stays inspectable and
carries no code-execution surface.
"""

from __future__ import annotations

from trino_tpu import types as T
from trino_tpu.expr.ir import AggCall, Call, Cast, InputRef, Literal
from trino_tpu.plan import nodes as P

__all__ = ["plan_to_json", "plan_from_json"]


# ---- types -----------------------------------------------------------------

def _t(t: T.DataType | None):
    return None if t is None else str(t)


def _t_back(s):
    if s is None:
        return None
    if s == "unknown":
        return T.UNKNOWN
    return T.type_from_name(s)


# ---- expressions -----------------------------------------------------------

def _expr(e):
    if e is None:
        return None
    if isinstance(e, Literal):
        v = e.value
        if isinstance(v, tuple):
            # ARRAY literals carry a tuple of storage-form scalars
            v = list(v)
        elif not (v is None or isinstance(v, (bool, int, float, str))):
            raise TypeError(f"unserializable literal {v!r}")
        return {"k": "lit", "t": _t(e.type), "v": v}
    if isinstance(e, InputRef):
        return {"k": "ref", "t": _t(e.type), "n": e.name}
    if isinstance(e, Call):
        return {
            "k": "call", "t": _t(e.type), "n": e.name,
            "a": [_expr(a) for a in e.args],
        }
    if isinstance(e, Cast):
        return {"k": "cast", "t": _t(e.type), "a": _expr(e.arg)}
    raise TypeError(f"unserializable expression {type(e).__name__}")


def _expr_back(d):
    if d is None:
        return None
    k = d["k"]
    if k == "lit":
        v = d["v"]
        if isinstance(v, list):
            v = tuple(v)
        return Literal(_t_back(d["t"]), v)
    if k == "ref":
        return InputRef(_t_back(d["t"]), d["n"])
    if k == "call":
        return Call(
            _t_back(d["t"]), d["n"],
            tuple(_expr_back(a) for a in d["a"]),
        )
    if k == "cast":
        return Cast(_t_back(d["t"]), _expr_back(d["a"]))
    raise ValueError(f"bad expression kind {k!r}")


def _agg(a: AggCall):
    return {
        "n": a.name, "a": [_expr(x) for x in a.args], "t": _t(a.type),
        "d": a.distinct, "f": _expr(a.filter),
    }


def _agg_back(d):
    return AggCall(
        d["n"], tuple(_expr_back(x) for x in d["a"]), _t_back(d["t"]),
        distinct=d["d"], filter=_expr_back(d["f"]),
    )


def _outputs(node: P.PlanNode):
    return [[s, _t(t)] for s, t in node.outputs.items()]


def _outputs_back(lst):
    return {s: _t_back(t) for s, t in lst}


def _sort_keys(keys):
    return [[k.symbol, k.ascending, k.nulls_first] for k in keys]


def _sort_keys_back(lst):
    return [P.SortKey(s, a, nf) for s, a, nf in lst]


# ---- plan nodes ------------------------------------------------------------

def _pair_ranges(ranges):
    """``Join.key_ranges`` / ``SemiJoin.key_ranges``: a criterion's two
    symbols, then its exact (lo, hi) — Python ints, which JSON carries
    whole."""
    if ranges is None:
        return None
    return [[l, r, lo, hi] for (l, r), (lo, hi) in ranges.items()]


def _pair_ranges_back(lst):
    if lst is None:
        return None
    return {(l, r): (lo, hi) for l, r, lo, hi in lst}


def plan_to_json(node: P.PlanNode) -> dict:
    d = {"kind": type(node).__name__, "outputs": _outputs(node)}
    if isinstance(node, P.TableScan):
        d.update(
            catalog=node.catalog, schema=node.schema, table=node.table,
            assignments=list(node.assignments.items()),
            hash_varchar=node.hash_varchar,
        )
        if node.split is not None:
            d.update(split=list(node.split))
        if node.domains is not None:
            d.update(domains=[
                [c, list(dom)] for c, dom in node.domains.items()
            ])
        return d
    if isinstance(node, P.RemoteSource):
        d.update(source_id=node.source_id)
        return d
    if isinstance(node, P.Values):
        d.update(rows=node.rows)
        return d
    if isinstance(node, P.Filter):
        d.update(source=plan_to_json(node.source), predicate=_expr(node.predicate))
        return d
    if isinstance(node, P.Project):
        d.update(
            source=plan_to_json(node.source),
            assignments=[[s, _expr(e)] for s, e in node.assignments.items()],
        )
        return d
    if isinstance(node, P.Aggregate):
        d.update(
            source=plan_to_json(node.source),
            group_keys=list(node.group_keys),
            aggregates=[[s, _agg(a)] for s, a in node.aggregates.items()],
            step=node.step, est_groups=node.est_groups,
            key_ranges=(
                None if node.key_ranges is None
                else list(node.key_ranges.items())
            ),
        )
        return d
    if isinstance(node, P.Join):
        d.update(
            kind2=node.kind, left=plan_to_json(node.left),
            right=plan_to_json(node.right),
            criteria=[list(c) for c in node.criteria],
            filter=_expr(node.filter), distribution=node.distribution,
            df_range_keep=node.df_range_keep,
            df_keep_frac=node.df_keep_frac,
            key_ranges=_pair_ranges(node.key_ranges),
        )
        return d
    if isinstance(node, P.SemiJoin):
        d.update(
            source=plan_to_json(node.source),
            filter_source=plan_to_json(node.filter_source),
            keys=[list(k) for k in node.keys],
            match_symbol=node.match_symbol, filter=_expr(node.filter),
            null_aware=node.null_aware,
            key_ranges=_pair_ranges(node.key_ranges),
        )
        return d
    if isinstance(node, P.Window):
        d.update(
            source=plan_to_json(node.source),
            partition_by=list(node.partition_by),
            order_keys=_sort_keys(node.order_keys),
            functions=[
                [
                    s,
                    {
                        "n": c.name, "a": [_expr(a) for a in c.args],
                        "t": _t(c.type), "frame": c.frame,
                    },
                ]
                for s, c in node.functions.items()
            ],
        )
        return d
    if isinstance(node, P.Union):
        d.update(
            all_sources=[plan_to_json(s) for s in node.all_sources],
            symbol_map=[[s, list(v)] for s, v in node.symbol_map.items()],
        )
        return d
    if isinstance(node, P.GroupId):
        d.update(
            source=plan_to_json(node.source),
            grouping_sets=[list(st) for st in node.grouping_sets],
            id_symbol=node.id_symbol,
        )
        return d
    if isinstance(node, P.Unnest):
        d.update(
            source=plan_to_json(node.source),
            arrays=[
                [_expr(e) for e in a] if isinstance(a, tuple)
                else {"ref": _expr(a)}
                for a in node.arrays
            ],
            element_symbols=list(node.element_symbols),
        )
        return d
    if isinstance(node, (P.Sort, P.TopN)):
        d.update(source=plan_to_json(node.source), keys=_sort_keys(node.keys))
        if isinstance(node, P.TopN):
            d.update(count=node.count)
        return d
    if isinstance(node, P.Limit):
        d.update(
            source=plan_to_json(node.source), count=node.count,
            offset=node.offset,
        )
        return d
    if isinstance(node, P.Exchange):
        d.update(
            source=plan_to_json(node.source),
            partitioning=node.partitioning,
            hash_symbols=list(node.hash_symbols), scope=node.scope,
            input_dist=node.input_dist, ordered=node.ordered,
            sort_keys=(
                None if node.sort_keys is None
                else _sort_keys(node.sort_keys)
            ),
        )
        return d
    if isinstance(node, P.Output):
        d.update(
            source=plan_to_json(node.source), names=list(node.names),
            symbols=list(node.symbols),
        )
        return d
    if isinstance(node, P.TableWriter):
        d.update(
            source=plan_to_json(node.source), handle=dict(node.handle),
            columns=list(node.columns),
        )
        return d
    if isinstance(node, P.TableFinish):
        d.update(
            source=plan_to_json(node.source), handle=dict(node.handle),
        )
        return d
    raise TypeError(f"unserializable plan node {type(node).__name__}")


def plan_from_json(d: dict) -> P.PlanNode:
    kind = d["kind"]
    outputs = _outputs_back(d["outputs"])
    if kind == "TableScan":
        return P.TableScan(
            outputs, catalog=d["catalog"], schema=d["schema"],
            table=d["table"], assignments=dict(d["assignments"]),
            hash_varchar=d.get("hash_varchar"),
            split=(tuple(d["split"]) if d.get("split") else None),
            domains=(
                {c: tuple(dom) for c, dom in d["domains"]}
                if d.get("domains") else None
            ),
        )
    if kind == "RemoteSource":
        return P.RemoteSource(outputs, source_id=d["source_id"])
    if kind == "Values":
        return P.Values(outputs, rows=d["rows"])
    if kind == "Filter":
        return P.Filter(
            outputs, source=plan_from_json(d["source"]),
            predicate=_expr_back(d["predicate"]),
        )
    if kind == "Project":
        return P.Project(
            outputs, source=plan_from_json(d["source"]),
            assignments={s: _expr_back(e) for s, e in d["assignments"]},
        )
    if kind == "Aggregate":
        return P.Aggregate(
            outputs, source=plan_from_json(d["source"]),
            group_keys=list(d["group_keys"]),
            aggregates={s: _agg_back(a) for s, a in d["aggregates"]},
            step=d["step"], est_groups=d["est_groups"],
            key_ranges=(
                None if d["key_ranges"] is None
                else {s: tuple(r) for s, r in d["key_ranges"]}
            ),
        )
    if kind == "Join":
        return P.Join(
            outputs, kind=d["kind2"],
            left=plan_from_json(d["left"]),
            right=plan_from_json(d["right"]),
            criteria=[tuple(c) for c in d["criteria"]],
            filter=_expr_back(d["filter"]),
            distribution=d["distribution"],
            df_range_keep=d["df_range_keep"],
            df_keep_frac=d["df_keep_frac"],
            key_ranges=_pair_ranges_back(d["key_ranges"]),
        )
    if kind == "SemiJoin":
        return P.SemiJoin(
            outputs, source=plan_from_json(d["source"]),
            filter_source=plan_from_json(d["filter_source"]),
            keys=[tuple(k) for k in d["keys"]],
            match_symbol=d["match_symbol"],
            filter=_expr_back(d["filter"]), null_aware=d["null_aware"],
            key_ranges=_pair_ranges_back(d["key_ranges"]),
        )
    if kind == "Window":
        return P.Window(
            outputs, source=plan_from_json(d["source"]),
            partition_by=list(d["partition_by"]),
            order_keys=_sort_keys_back(d["order_keys"]),
            functions={
                s: P.WindowCall(
                    c["n"], tuple(_expr_back(a) for a in c["a"]),
                    _t_back(c["t"]),
                    frame=(
                        None if c["frame"] is None
                        else _frame_back(c["frame"])
                    ),
                )
                for s, c in d["functions"]
            },
        )
    if kind == "Union":
        return P.Union(
            outputs,
            all_sources=[plan_from_json(s) for s in d["all_sources"]],
            symbol_map={s: list(v) for s, v in d["symbol_map"]},
        )
    if kind == "GroupId":
        return P.GroupId(
            outputs, source=plan_from_json(d["source"]),
            grouping_sets=[list(st) for st in d["grouping_sets"]],
            id_symbol=d["id_symbol"],
        )
    if kind == "Unnest":
        return P.Unnest(
            outputs, source=plan_from_json(d["source"]),
            arrays=[
                _expr_back(a["ref"]) if isinstance(a, dict)
                else tuple(_expr_back(e) for e in a)
                for a in d["arrays"]
            ],
            element_symbols=list(d["element_symbols"]),
        )
    if kind == "Sort":
        return P.Sort(
            outputs, source=plan_from_json(d["source"]),
            keys=_sort_keys_back(d["keys"]),
        )
    if kind == "TopN":
        return P.TopN(
            outputs, source=plan_from_json(d["source"]),
            count=d["count"], keys=_sort_keys_back(d["keys"]),
        )
    if kind == "Limit":
        return P.Limit(
            outputs, source=plan_from_json(d["source"]),
            count=d["count"], offset=d["offset"],
        )
    if kind == "Exchange":
        return P.Exchange(
            outputs, source=plan_from_json(d["source"]),
            partitioning=d["partitioning"],
            hash_symbols=list(d["hash_symbols"]), scope=d["scope"],
            input_dist=d["input_dist"], ordered=d.get("ordered", False),
            sort_keys=(
                None if d.get("sort_keys") is None
                else _sort_keys_back(d["sort_keys"])
            ),
        )
    if kind == "Output":
        return P.Output(
            outputs, source=plan_from_json(d["source"]),
            names=list(d["names"]), symbols=list(d["symbols"]),
        )
    if kind == "TableWriter":
        return P.TableWriter(
            outputs, source=plan_from_json(d["source"]),
            handle=dict(d["handle"]), columns=list(d["columns"]),
        )
    if kind == "TableFinish":
        return P.TableFinish(
            outputs, source=plan_from_json(d["source"]),
            handle=dict(d["handle"]),
        )
    raise ValueError(f"bad plan node kind {kind!r}")


def _frame_back(frame):
    """Window frames are (mode, (kind, off), (kind, off)) tuples; JSON
    turns the tuples into lists."""
    mode, start, end = frame
    return (mode, tuple(start), tuple(end))
