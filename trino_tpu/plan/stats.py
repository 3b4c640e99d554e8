"""Plan statistics: cardinality + per-symbol value-domain estimation.

The analog of the reference's StatsCalculator stack (MAIN/cost/:
FilterStatsCalculator.java, JoinStatsRule.java,
AggregationStatsRule.java) collapsed into one recursive pass. Two
consumers with different contracts:

- **Cardinality** (``PlanStats.rows``, per-symbol ``ndv``) is an
  *estimate* — used for join ordering, build-side choice,
  broadcast-vs-partitioned and aggregation capacity planning. Being
  wrong costs performance, never correctness.
- **Value bounds** (``lo``/``hi`` with ``exact=True``) are
  *guarantees* — the executor packs group-by keys into
  ``bit_length(hi - lo)`` bits (value-range key packing), so a live
  row outside the claimed range would corrupt grouping. Bounds start
  from connector-exact table stats and are only narrowed by predicates
  that are *guaranteed applied* beneath the consuming node; anything
  uncertain drops exactness.

Bounds/ndv live in the column's storage order-domain: ints as-is,
dates as day numbers, decimals as unscaled ints, doubles as floats
(varchar carries ndv only).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

from trino_tpu import types as T
from trino_tpu.expr.ir import Call, Cast, InputRef, Literal, RowExpression
from trino_tpu.metadata import Metadata
from trino_tpu.plan import nodes as P

__all__ = ["SymbolStats", "PlanStats", "estimate", "annotate"]

#: selectivity for predicates the calculator cannot reason about
#: (the reference's UNKNOWN_FILTER_COEFFICIENT is 0.9; 0.5 is chosen
#: because unfiltered over-estimates only waste capacity while
#: under-estimates trigger overflow retries)
UNKNOWN_FILTER_COEFFICIENT = 0.5


@dataclass(frozen=True)
class SymbolStats:
    ndv: float | None = None
    lo: float | None = None
    hi: float | None = None
    null_frac: float = 0.0
    #: True when lo/hi are guaranteed bounds (see module docstring)
    exact: bool = False

    @property
    def range_width(self) -> float | None:
        if self.lo is None or self.hi is None:
            return None
        return self.hi - self.lo


_UNKNOWN = SymbolStats()


class _ScanColumn:
    """One column of a scanned table, described by its connector when a
    symbol over it is first looked up — and never, where no estimate
    asks: an estimate is first taken before pruning, when a scan still
    assigns every column of its table, and a generator connector
    computes a column's statistics from the whole column (at SF5
    ``l_comment`` alone is minutes and gigabytes)."""

    __slots__ = ("_ask", "_stats")

    def __init__(self, conn, schema: str, table: str, column: str):
        self._ask = (conn, schema, table, column)
        self._stats: SymbolStats | None = None

    def stats(self) -> SymbolStats:
        if self._stats is None:
            conn, schema, table, column = self._ask
            try:
                cs = conn.column_stats(schema, table, column)
            except Exception:
                cs = None
            self._stats = _UNKNOWN if cs is None else SymbolStats(
                ndv=cs.ndv, lo=cs.lo, hi=cs.hi,
                null_frac=cs.null_fraction,
                exact=cs.lo is not None,
            )
        return self._stats


class _Symbols(dict):
    """symbol -> SymbolStats. Beside the entries a dict holds it
    carries the scan columns nobody has looked up yet (``_pending``:
    symbol -> (column, the row estimate its ndv is capped at, or
    None)); ``get`` / ``[]`` describe such a column on first lookup
    and move it among the entries; iteration, ``in`` and ``len`` see
    the entries alone. Copies and merges hand pending columns on
    undescribed, so the answer is what an eager description at the
    scan would have given."""

    __slots__ = ("_pending",)

    def __init__(self, known=(), pending=()):
        super().__init__(known)
        self._pending: dict[str, tuple[_ScanColumn, float | None]] = dict(
            pending
        )

    def _describe(self, sym: str) -> None:
        hit = self._pending.pop(sym, None)
        if hit is not None:
            st, cap = hit[0].stats(), hit[1]
            if cap is not None and st.ndv is not None and st.ndv > cap:
                st = replace(st, ndv=cap)
            super().__setitem__(sym, st)

    def get(self, sym, default=None):
        self._describe(sym)
        return super().get(sym, default)

    def __getitem__(self, sym):
        self._describe(sym)
        return super().__getitem__(sym)

    def __setitem__(self, sym, st):
        self._pending.pop(sym, None)
        super().__setitem__(sym, st)

    def copy(self) -> "_Symbols":
        return _Symbols(self, self._pending)

    def merged(self, other: "_Symbols") -> "_Symbols":
        """``{**self, **other}``: on a symbol of both, ``other``'s."""
        out = self.copy()
        for sym in other:
            out._pending.pop(sym, None)
        for sym in other._pending:
            dict.pop(out, sym, None)
        dict.update(out, other)
        out._pending.update(other._pending)
        return out

    def alias(self, sym: str, src: "_Symbols", name: str) -> None:
        """``self[sym] = src[name]``, a pending column left pending."""
        hit = src._pending.get(name)
        if hit is not None:
            dict.pop(self, sym, None)
            self._pending[sym] = hit
        else:
            self[sym] = src.get(name, _UNKNOWN)

    def cap_ndv(self, rows: float) -> None:
        """No symbol has more distinct values than there are rows."""
        for sym, st in self.items():
            if st.ndv is not None and st.ndv > rows:
                super().__setitem__(sym, replace(st, ndv=rows))
        for sym, (col, cap) in self._pending.items():
            self._pending[sym] = (col, rows if cap is None else min(cap, rows))


@dataclass(frozen=True)
class PlanStats:
    rows: float
    symbols: dict[str, SymbolStats] = field(default_factory=_Symbols)

    def __post_init__(self):
        if not isinstance(self.symbols, _Symbols):
            object.__setattr__(self, "symbols", _Symbols(self.symbols))

    def sym(self, name: str) -> SymbolStats:
        return self.symbols.get(name, _UNKNOWN)


def estimate(
    node: P.PlanNode, metadata: Metadata, _cache: dict | None = None
) -> PlanStats:
    """Estimate output stats of ``node`` (memoized by node identity)."""
    if _cache is None:
        _cache = {}
    hit = _cache.get(id(node))
    # entries pin the node object (id-keyed caches alias freed
    # addresses otherwise) and verify identity before use
    if hit is not None and hit[0] is node:
        return hit[1]
    out = _estimate(node, metadata, _cache)
    _cache[id(node)] = (node, out)
    return out


def _estimate(node, md, cache) -> PlanStats:
    if isinstance(node, P.TableScan):
        return _scan_stats(node, md)
    if isinstance(node, P.Values):
        return PlanStats(float(len(node.rows)))
    if isinstance(node, P.Filter):
        src = estimate(node.source, md, cache)
        return _filter_stats(src, node.predicate)
    if isinstance(node, P.Project):
        src = estimate(node.source, md, cache)
        symbols = _Symbols()
        for sym, e in node.assignments.items():
            if isinstance(e, InputRef):
                symbols.alias(sym, src.symbols, e.name)
            else:
                symbols[sym] = _expr_stats(e, src)
        return PlanStats(src.rows, symbols)
    if isinstance(node, P.Aggregate):
        return _aggregate_stats(node, md, cache)
    if isinstance(node, P.Join):
        return _join_stats(node, md, cache)
    if isinstance(node, P.SemiJoin):
        src = estimate(node.source, md, cache)
        filt = estimate(node.filter_source, md, cache)
        symbols = src.symbols.copy()
        symbols[node.match_symbol] = SymbolStats(ndv=2.0)
        # rows unchanged: the match symbol is a column; the Filter
        # above applies its selectivity (bare-boolean-ref path)
        return PlanStats(src.rows, symbols)
    if isinstance(node, P.Window):
        src = estimate(node.source, md, cache)
        symbols = src.symbols.copy()
        for sym, call in node.functions.items():
            symbols[sym] = _UNKNOWN
        return PlanStats(src.rows, symbols)
    if isinstance(node, P.Union):
        rows = 0.0
        branches = [estimate(s, md, cache) for s in node.all_sources]
        rows = sum(b.rows for b in branches)
        symbols = {}
        for sym, ins in node.symbol_map.items():
            per = [b.sym(i) for b, i in zip(branches, ins)]
            symbols[sym] = _union_sym(per)
        return PlanStats(rows, symbols)
    if isinstance(node, (P.Limit, P.TopN)):
        src = estimate(node.sources[0], md, cache)
        n = getattr(node, "count", -1)
        rows = min(float(n), src.rows) if n >= 0 else src.rows
        return PlanStats(rows, src.symbols.copy())
    if isinstance(node, (P.Sort, P.Output, P.Exchange)):
        src = estimate(node.sources[0], md, cache)
        return PlanStats(src.rows, src.symbols.copy())
    if isinstance(node, P.GroupId):
        src = estimate(node.source, md, cache)
        k = max(len(node.grouping_sets), 1)
        return PlanStats(src.rows * k, src.symbols.copy())
    if node.sources:
        src = estimate(node.sources[0], md, cache)
        return PlanStats(src.rows, {})
    return PlanStats(1.0)


def _scan_stats(node: P.TableScan, md: Metadata) -> PlanStats:
    try:
        conn = md.connector(node.catalog)
        rows = float(conn.row_count(node.schema, node.table))
    except Exception:
        return PlanStats(1e6)
    # column by column, and each on its first lookup: generator
    # connectors only materialize what an estimate asks for
    symbols = _Symbols(pending={
        sym: (_ScanColumn(conn, node.schema, node.table, col), None)
        for sym, col in node.assignments.items()
    })
    # pushdown domains narrow what the scan actually reads: clamp the
    # symbol bounds and scale the row estimate by the range fraction.
    # The Filter the domains came from stays in the plan and re-derives
    # its selectivity against the CLAMPED bounds (keep ~ 1), so the
    # reduction is applied once, at the scan where storage applies it.
    if node.domains:
        inv = {c: s for s, c in node.assignments.items()}
        for cname, dom in node.domains.items():
            sym = inv.get(cname)
            st = symbols.get(sym) if sym is not None else None
            if st is None or st.lo is None or st.hi is None:
                continue
            try:
                dlo = st.lo if dom[0] is None else float(dom[0])
                dhi = st.hi if dom[1] is None else float(dom[1])
            except (TypeError, ValueError):
                continue  # non-numeric domain (varchar partition key)
            nlo, nhi = max(float(st.lo), dlo), min(float(st.hi), dhi)
            if nhi < nlo:
                rows = 0.0
                continue
            width = float(st.hi) - float(st.lo)
            if width > 0:
                rows *= min(max((nhi - nlo) / width, 0.0), 1.0)
            symbols[sym] = replace(st, lo=nlo, hi=nhi)
    return PlanStats(max(rows, 1.0), symbols)


def _union_sym(per: list[SymbolStats]) -> SymbolStats:
    if any(s.ndv is None for s in per):
        return _UNKNOWN
    lo = hi = None
    exact = all(s.exact for s in per)
    if all(s.lo is not None for s in per):
        lo = min(s.lo for s in per)
        hi = max(s.hi for s in per)
    else:
        exact = False
    return SymbolStats(
        ndv=sum(s.ndv for s in per), lo=lo, hi=hi,
        null_frac=max(s.null_frac for s in per), exact=exact,
    )


# ---- filters ---------------------------------------------------------------

def _conjuncts(e: RowExpression) -> list[RowExpression]:
    if isinstance(e, Call) and e.name == "and":
        out = []
        for a in e.args:
            out.extend(_conjuncts(a))
        return out
    return [e]


def _literal_num(e: RowExpression) -> float | int | None:
    """Numeric order-domain value of a literal (unscaled for decimals,
    day number for dates)."""
    while isinstance(e, Cast):
        # a cast changes the domain (e.g. decimal rescale); only
        # identity-domain casts are safe to look through
        if not _same_domain(e.type, e.arg.type):
            return None
        e = e.arg
    if not isinstance(e, Literal) or e.value is None:
        return None
    if isinstance(e.type, T.VarcharType):
        return None
    from trino_tpu.expr.compiler import _literal_device_value

    try:
        v = _literal_device_value(e)
    except Exception:
        return None
    if isinstance(v, bool):
        return int(v)
    if isinstance(v, int):
        return v  # keep exact: float64 rounds beyond 2^53
    if isinstance(v, float):
        return v
    return None


def _same_domain(a: T.DataType, b: T.DataType) -> bool:
    if isinstance(a, T.DecimalType) or isinstance(b, T.DecimalType):
        return (
            isinstance(a, T.DecimalType)
            and isinstance(b, T.DecimalType)
            and a.scale == b.scale
        )
    return True


def _plain_ref(e: RowExpression) -> str | None:
    if isinstance(e, InputRef):
        return e.name
    return None


def _filter_stats(src: PlanStats, predicate: RowExpression | None) -> PlanStats:
    if predicate is None:
        return src
    rows = src.rows
    symbols = src.symbols.copy()
    for c in _conjuncts(predicate):
        sel = _apply_conjunct(c, symbols)
        rows *= sel
    rows = max(rows, 1.0)
    # cap every ndv at the new row estimate
    symbols.cap_ndv(rows)
    return PlanStats(rows, symbols)


def _apply_conjunct(c: RowExpression, symbols: dict) -> float:
    """Selectivity of one conjunct; narrows symbol bounds in place.
    Bounds narrowed here keep ``exact=True``: a conjunct only narrows
    the symbol it directly constrains, and every surviving row
    satisfies it."""
    if isinstance(c, Call) and c.name in ("eq", "ne", "lt", "le", "gt", "ge"):
        a, b = c.args
        ra, rb = _plain_ref(a), _plain_ref(b)
        va, vb = _literal_num(a), _literal_num(b)
        if ra is not None and vb is not None:
            return _range_conjunct(c.name, ra, vb, symbols)
        if rb is not None and va is not None:
            flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}
            return _range_conjunct(
                flip.get(c.name, c.name), rb, va, symbols
            )
        if c.name == "eq" and ra is not None and rb is not None:
            na = symbols.get(ra, _UNKNOWN).ndv
            nb = symbols.get(rb, _UNKNOWN).ndv
            if na and nb:
                return 1.0 / max(na, nb)
        return UNKNOWN_FILTER_COEFFICIENT
    if isinstance(c, Call) and c.name == "between":
        x, lo, hi = c.args
        r = _plain_ref(x)
        vlo, vhi = _literal_num(lo), _literal_num(hi)
        if r is not None and vlo is not None and vhi is not None:
            s1 = _range_conjunct("ge", r, vlo, symbols)
            s2 = _range_conjunct("le", r, vhi, symbols)
            return s1 * s2
        return UNKNOWN_FILTER_COEFFICIENT
    if isinstance(c, Call) and c.name == "in":
        x = c.args[0]
        r = _plain_ref(x)
        vals = [_literal_num(a) for a in c.args[1:]]
        if r is not None and all(v is not None for v in vals) and vals:
            st = symbols.get(r, _UNKNOWN)
            if st.ndv:
                sel = min(1.0, len(set(vals)) / st.ndv)
            else:
                sel = UNKNOWN_FILTER_COEFFICIENT
            lo, hi = min(vals), max(vals)
            symbols[r] = replace(
                st,
                lo=lo if st.lo is None else max(st.lo, lo),
                hi=hi if st.hi is None else min(st.hi, hi),
                ndv=min(st.ndv, len(set(vals))) if st.ndv else None,
                null_frac=0.0,
            )
            return sel
        return UNKNOWN_FILTER_COEFFICIENT
    if isinstance(c, Call) and c.name == "is_null":
        r = _plain_ref(c.args[0])
        if r is not None:
            st = symbols.get(r, _UNKNOWN)
            return st.null_frac if st.ndv is not None else 0.1
        return 0.1
    if isinstance(c, Call) and c.name == "not":
        inner = c.args[0]
        if isinstance(inner, Call) and inner.name == "is_null":
            r = _plain_ref(inner.args[0])
            if r is not None:
                st = symbols.get(r, _UNKNOWN)
                symbols[r] = replace(st, null_frac=0.0)
                return 1.0 - st.null_frac
            return 0.9
        # NOT(x): bounds inside must not narrow — evaluate on a scratch
        scratch = symbols.copy()
        return max(0.0, 1.0 - _apply_conjunct(inner, scratch))
    if isinstance(c, Call) and c.name == "or":
        # independence-union; bounds must not narrow (either branch
        # may hold)
        remaining = 1.0
        for b in c.args:
            scratch = symbols.copy()
            s = _apply_conjunct(b, scratch)
            remaining *= 1.0 - s
        return min(1.0, 1.0 - remaining)
    if isinstance(c, Call) and c.name == "like":
        return 0.25
    if isinstance(c, InputRef):
        # bare boolean column (e.g. a semi-join match symbol)
        st = symbols.get(c.name, _UNKNOWN)
        if st.ndv == 2.0:
            return 0.5
        return UNKNOWN_FILTER_COEFFICIENT
    return UNKNOWN_FILTER_COEFFICIENT


def _range_conjunct(op: str, sym: str, v: float, symbols: dict) -> float:
    st = symbols.get(sym, _UNKNOWN)
    lo, hi, ndv = st.lo, st.hi, st.ndv
    nonnull = 1.0 - st.null_frac
    if op == "eq":
        symbols[sym] = replace(st, lo=v, hi=v, ndv=1.0, null_frac=0.0)
        return (1.0 / ndv) * nonnull if ndv else 0.1
    if op == "ne":
        if ndv:
            return (1.0 - 1.0 / ndv) * nonnull
        return 0.9
    if lo is None or hi is None or hi <= lo:
        # unknown or single-valued domain
        sel = UNKNOWN_FILTER_COEFFICIENT
        if lo is not None and hi is not None and hi == lo:
            holds = {
                "lt": lo < v, "le": lo <= v, "gt": lo > v, "ge": lo >= v,
            }[op]
            sel = nonnull if holds else 0.0
        return sel
    width = hi - lo
    if op in ("lt", "le"):
        frac = (v - lo) / width
        new_hi = min(hi, v)
        symbols[sym] = replace(
            st, hi=new_hi,
            ndv=ndv * min(max(frac, 0.0), 1.0) if ndv else None,
            null_frac=0.0,
        )
    else:
        frac = (hi - v) / width
        new_lo = max(lo, v)
        symbols[sym] = replace(
            st, lo=new_lo,
            ndv=ndv * min(max(frac, 0.0), 1.0) if ndv else None,
            null_frac=0.0,
        )
    return min(max(frac, 0.0), 1.0) * nonnull


def _expr_stats(e: RowExpression, src: PlanStats) -> SymbolStats:
    """Derived-expression stats: conservative (no exact bounds except
    trivially safe forms)."""
    if isinstance(e, Cast):
        inner = _expr_stats(e.arg, src)
        if _same_domain(e.type, e.arg.type):
            return inner
        return replace(inner, lo=None, hi=None, exact=False)
    if isinstance(e, InputRef):
        return src.sym(e.name)
    if isinstance(e, Literal):
        v = _literal_num(e)
        if v is None:
            return SymbolStats(ndv=1.0)
        return SymbolStats(ndv=1.0, lo=v, hi=v, exact=True)
    return _UNKNOWN


# ---- aggregates / joins ----------------------------------------------------

def _aggregate_stats(node: P.Aggregate, md, cache) -> PlanStats:
    src = estimate(node.source, md, cache)
    if not node.group_keys:
        return PlanStats(1.0, {
            sym: SymbolStats(ndv=1.0) for sym in node.aggregates
        })
    groups = 1.0
    known = False
    for k in node.group_keys:
        ndv = src.sym(k).ndv
        if ndv:
            groups *= max(ndv, 1.0)
            known = True
    if not known:
        groups = max(src.rows / 10.0, 1.0)
    rows = min(groups, src.rows)
    symbols = {k: src.sym(k) for k in node.group_keys}
    for sym, call in node.aggregates.items():
        if call.name in ("count", "count_all", "count_if", "count_final"):
            symbols[sym] = SymbolStats(lo=0.0, null_frac=0.0)
        else:
            symbols[sym] = _UNKNOWN
    return PlanStats(rows, symbols)


def _join_stats(node: P.Join, md, cache) -> PlanStats:
    l = estimate(node.left, md, cache)
    r = estimate(node.right, md, cache)
    symbols = l.symbols.merged(r.symbols)
    if node.kind == "cross" or not node.criteria:
        rows = l.rows * r.rows
    else:
        rows = l.rows * r.rows
        for a, b in node.criteria:
            na, nb = l.sym(a).ndv, r.sym(b).ndv
            denom = max(na or 0.0, nb or 0.0)
            if denom <= 0:
                denom = max(min(l.rows, r.rows), 1.0)
            rows /= denom
            if node.kind == "inner":
                # only an inner join guarantees surviving rows matched
                # BOTH sides; outer joins keep unmatched rows whose
                # keys lie outside the other side's range (and may be
                # NULL-extended), so intersected exact bounds would
                # corrupt value-range key packing
                joined = _intersect_sym(l.sym(a), r.sym(b))
                symbols[a] = joined
                symbols[b] = joined
        rows = max(rows, 1.0)
    if node.kind == "left":
        rows = max(rows, l.rows)
    elif node.kind == "right":
        rows = max(rows, r.rows)
    elif node.kind == "full":
        rows = max(rows, l.rows + r.rows)
    if node.filter is not None:
        rows *= UNKNOWN_FILTER_COEFFICIENT
    return PlanStats(max(rows, 1.0), symbols)


def _intersect_sym(a: SymbolStats, b: SymbolStats) -> SymbolStats:
    ndv = None
    if a.ndv is not None and b.ndv is not None:
        ndv = min(a.ndv, b.ndv)
    a_full = a.lo is not None and a.hi is not None
    b_full = b.lo is not None and b.hi is not None
    if a.exact != b.exact:
        # one side's bounds are a guarantee and the other's an
        # estimate: the guarantee alone stays one (an estimate may cut
        # into the live keys, and the result is claimed exact)
        a_full, b_full = a_full and a.exact, b_full and b.exact
    lo = hi = None
    if a_full and b_full:
        lo, hi = max(a.lo, b.lo), min(a.hi, b.hi)
    elif a_full:
        lo, hi = a.lo, a.hi
    elif b_full:
        lo, hi = b.lo, b.hi
    return SymbolStats(
        ndv=ndv, lo=lo, hi=hi, null_frac=0.0,
        # the joined column only keeps rows from BOTH inputs, so either
        # side's exact bounds alone still bound it
        exact=a.exact or b.exact,
    )


# ---- plan annotation -------------------------------------------------------

#: varchar columns beyond this NDV scan hash-coded when eligible
#: (session ``varchar_hash_ndv`` overrides; the sorted-dictionary
#: build is an O(n log n) host string sort — the SF1 l_comment cliff)
VARCHAR_HASH_NDV = 1 << 20


def _hash_varchar_candidates(plan: P.PlanNode, metadata, threshold):
    """Scan symbols eligible for hash-coded varchar: used ONLY as group
    keys, plain join criteria (both sides eligible), count/distinct
    arguments, or raw output — never in ordering, range/LIKE
    predicates, projections or other expressions (those need sorted
    dictionary codes)."""
    from trino_tpu.expr.ir import InputRef as Ref

    scans: dict[str, tuple[P.TableScan, str]] = {}
    unsafe: set[str] = set()
    join_edges: list[tuple[str, str]] = []
    #: identity-projection renames (out symbol -> source symbol):
    #: unsafety flows back through them to the scan symbol
    aliases: list[tuple[str, str]] = []

    def expr_refs(e):
        out = set()

        def w(x):
            if isinstance(x, Ref):
                out.add(x.name)
            for a in getattr(x, "args", ()):
                w(a)
            arg = getattr(x, "arg", None)
            if arg is not None:
                w(arg)

        if e is not None:
            w(e)
        return out

    def walk(node):
        for s in node.sources:
            walk(s)
        if isinstance(node, P.TableScan):
            for sym, col in node.assignments.items():
                if isinstance(node.outputs.get(sym), T.VarcharType):
                    scans[sym] = (node, col)
            return
        if isinstance(node, P.Filter):
            unsafe.update(expr_refs(node.predicate))
        elif isinstance(node, P.Project):
            for out_sym, e in node.assignments.items():
                if isinstance(e, Ref):
                    aliases.append((out_sym, e.name))
                else:
                    unsafe.update(expr_refs(e))
        elif isinstance(node, P.Aggregate):
            for call in node.aggregates.values():
                names = set()
                for a in call.args:
                    names |= expr_refs(a)
                names |= expr_refs(call.filter)
                if call.name not in ("count", "count_all"):
                    unsafe.update(names)
                elif not all(isinstance(a, Ref) for a in call.args):
                    unsafe.update(names)
        elif isinstance(node, P.Join):
            join_edges.extend(node.criteria)
            unsafe.update(expr_refs(node.filter))
        elif isinstance(node, P.SemiJoin):
            join_edges.extend(node.keys)
            unsafe.update(expr_refs(node.filter))
        elif isinstance(node, (P.Sort, P.TopN)):
            unsafe.update(k.symbol for k in node.keys)
        elif isinstance(node, P.Window):
            unsafe.update(k.symbol for k in node.order_keys)
            # partition keys are equality-style, but the window
            # executor has no hash-lane handling yet
            unsafe.update(node.partition_by)
            for call in node.functions.values():
                for a in call.args:
                    unsafe.update(expr_refs(a))
        elif isinstance(node, P.Unnest):
            for a in node.arrays:
                for e in (a if isinstance(a, tuple) else (a,)):
                    unsafe.update(expr_refs(e))
        elif isinstance(node, P.Union):
            for ins in node.symbol_map.values():
                unsafe.update(ins)  # branch remaps need dictionaries

    walk(plan)
    # unsafety propagates backwards through identity renames to the
    # scan symbol (ORDER BY on an alias is an ordered use of the base)
    changed = True
    while changed:
        changed = False
        for out_sym, in_sym in aliases:
            if out_sym in unsafe and in_sym not in unsafe:
                unsafe.add(in_sym)
                changed = True

    def eligible(sym):
        if sym in unsafe or sym not in scans:
            return False
        node, col = scans[sym]
        try:
            cs = metadata.connector(node.catalog).column_stats(
                node.schema, node.table, col
            )
        except Exception:
            return False
        return cs is not None and cs.ndv is not None and cs.ndv > threshold

    # join-connected symbols hash together or not at all (a mixed
    # hash/dictionary join would need cross-encoding remaps); an edge
    # touching any symbol we cannot prove hash-eligible (including
    # renamed/derived ones) disqualifies its partner too
    chosen = {s for s in scans if eligible(s)}
    # resolve projection renames back to base symbols so an aliased
    # join edge still couples (or disqualifies) its endpoints
    alias_to_base = {}
    for out_sym, in_sym in aliases:
        alias_to_base[out_sym] = in_sym

    def base_of(sym):
        seen = set()
        while sym in alias_to_base and sym not in seen:
            seen.add(sym)
            sym = alias_to_base[sym]
        return sym

    changed = True
    while changed:
        changed = False
        for a0, b0 in join_edges:
            a, b = base_of(a0), base_of(b0)
            if a not in scans and b not in scans:
                continue
            if not (a in chosen and b in chosen):
                for s in (a, b):
                    if s in chosen:
                        chosen.discard(s)
                        changed = True
    for sym in chosen:
        node, _ = scans[sym]
        node.hash_varchar = sorted(
            set(node.hash_varchar or []) | {sym}
        )


def annotate(
    plan: P.PlanNode, metadata: Metadata, session=None
) -> P.PlanNode:
    """Annotate the final plan with executor-facing statistics:

    - ``Aggregate.est_groups``: expected distinct group count — sizes
      the group table upfront so capacity-overflow retries become rare
      (the reference reserves FlatHash capacity from stats the same
      way).
    - ``Aggregate.key_ranges``: {symbol: (lo, hi)} EXACT integer value
      bounds for group keys — the executor packs keys into
      bit_length(hi-lo) bits, turning multi-pass lexsorts into single
      u64 sort passes (value-range key packing, BASELINE.md).
    - ``Join.key_ranges`` / ``SemiJoin.key_ranges``: {(left symbol,
      right symbol): (lo, hi)} for each equi criterion whose two
      symbols BOTH carry exact integer bounds — one range that holds
      every live, non-NULL key of either input. It is read off the
      inputs, before the join, so it holds for every join kind (an
      outer join's unmatched rows are rows of an input). The executor
      ranks such a join's keys at bit_length(hi - lo) bits instead of
      64 (``kernels.join_ranges``).

    Mutates nodes in place (annotation fields only) and returns plan.
    """
    cache: dict = {}

    def walk(node: P.PlanNode):
        for s in node.sources:
            walk(s)
        if isinstance(node, P.Join) and node.criteria and node.kind == "inner":
            l = estimate(node.left, metadata, cache)
            r = estimate(node.right, metadata, cache)
            range_keep = 1.0
            member_keep = 1.0
            known = False
            for a, b in node.criteria:
                sa, sb = l.sym(a), r.sym(b)
                if sa.ndv and sb.ndv:
                    member_keep = min(
                        member_keep, min(1.0, sb.ndv / sa.ndv)
                    )
                    known = True
                if (
                    sa.lo is not None and sa.hi is not None
                    and sb.lo is not None and sb.hi is not None
                    and sa.hi > sa.lo
                ):
                    overlap = max(
                        0.0, min(sa.hi, sb.hi) - max(sa.lo, sb.lo)
                    )
                    range_keep = min(
                        range_keep, overlap / (sa.hi - sa.lo)
                    )
            node.df_range_keep = (
                range_keep if known or range_keep < 1.0 else None
            )
            node.df_keep_frac = member_keep if known else None
        if isinstance(node, (P.Join, P.SemiJoin)):
            sides = node.sources
            pairs = node.criteria if isinstance(node, P.Join) else node.keys
            stats = [estimate(s, metadata, cache) for s in sides]
            ranges = {}
            for pair in pairs:
                both = [
                    _exact_int_range(st.sym(k), side.outputs.get(k))
                    for k, st, side in zip(pair, stats, sides)
                ]
                if None not in both:
                    (lo_l, hi_l), (lo_r, hi_r) = both
                    ranges[tuple(pair)] = (min(lo_l, lo_r), max(hi_l, hi_r))
            node.key_ranges = ranges or None
        if isinstance(node, P.Aggregate) and node.group_keys:
            src = estimate(node.source, metadata, cache)
            groups = estimate(node, metadata, cache).rows
            node.est_groups = groups
            ranges = {}
            for k in node.group_keys:
                rng = _exact_int_range(src.sym(k), node.outputs.get(k))
                if rng is not None:
                    ranges[k] = rng
            node.key_ranges = ranges or None

    walk(plan)
    threshold = VARCHAR_HASH_NDV
    budgeted = False
    if session is not None:
        threshold = int(
            session.properties.get("varchar_hash_ndv", threshold)
        )
        # streamed (budget-mode) scans chunk per Split and would build
        # chunk-local pools mixing with resident hash columns; hash
        # coding stays off under a budget until the streamed path
        # carries pools
        budgeted = bool(session.properties.get("hbm_budget_bytes"))
    if threshold > 0 and not budgeted:
        _hash_varchar_candidates(plan, metadata, threshold)
    return plan


def _exact_int_range(st: SymbolStats, t: T.DataType | None):
    """``(lo, hi)`` where a key symbol's bounds are a guarantee over an
    integer domain — what a key may be packed or ranked by — else
    None."""
    if not st.exact or st.lo is None or st.hi is None:
        return None
    if t is None or not _int_domain(t):
        return None
    # int bounds stay ints through the whole stats chain; a float here
    # means something lossy touched them — never pack on a
    # possibly-rounded bound
    if not (isinstance(st.lo, int) and isinstance(st.hi, int)):
        return None
    return (st.lo, st.hi) if st.hi >= st.lo else None


def _int_domain(t: T.DataType) -> bool:
    """Types whose storage is an integer domain where (value - lo) is
    meaningful and bounded: ints, dates, timestamps, decimals. Varchar
    uses dictionary codes (handled separately); floats excluded (bit
    patterns are not contiguous), and two-limb decimals (their storage
    is a pair of words, not one)."""
    import numpy as np

    if isinstance(t, T.VarcharType) or isinstance(t, T.BooleanType):
        return False
    if isinstance(t, T.DecimalType) and t.is_long:
        return False
    return np.dtype(t.np_dtype).kind == "i"
