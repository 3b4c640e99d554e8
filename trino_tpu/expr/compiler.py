"""Expression -> jittable column-function compiler.

The analog of the reference's runtime bytecode generation
(MAIN/sql/gen/ExpressionCompiler.java:56, PageFunctionCompiler.java:102):
instead of emitting JVM bytecode per query, we trace typed
RowExpressions into closures over jax.numpy ops. The closure evaluates
a whole column at once; XLA fuses the resulting elementwise graph into
the surrounding kernel.

Null semantics: every evaluation returns ``(data, valid)`` where
``valid`` is a boolean array or None (all valid). Logic ops implement
SQL three-valued (Kleene) truth tables.

Strings: device data is dictionary codes. String-content functions
(LIKE, substr, lower, ...) are evaluated *over the dictionary values on
host at compile time* — a LIKE becomes a boolean lookup table indexed
by code, a substr becomes a code-remap gather. Each compiles to O(dict)
host work once plus an O(n) device gather, replacing per-row string
processing entirely (the dictionary-encode-early strategy from
SURVEY.md §7).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.expr.ir import Call, Cast, InputRef, Literal, RowExpression
from trino_tpu.page import StringDictionary

__all__ = ["ColumnLayout", "CompiledExpr", "compile_expr"]

# evaluation environment: name -> (data, valid|None)
Env = dict[str, tuple[jnp.ndarray, jnp.ndarray | None]]


@dataclass
class ColumnLayout:
    """Input layout a compilation binds to: types + dictionaries.

    The cache key role of (expression, input layout) mirrors
    PageFunctionCompiler's cache keyed on RowExpression + channels.
    """

    types: dict[str, T.DataType] = field(default_factory=dict)
    dictionaries: dict[str, StringDictionary | None] = field(default_factory=dict)
    #: host ArrayPools of ARRAY-typed input columns (page.ArrayPool);
    #: array functions compile host LUTs over the pool and gather by
    #: the device handle lanes
    array_pools: dict = field(default_factory=dict)


@dataclass
class CompiledExpr:
    fn: Callable[[Env], tuple[jnp.ndarray, jnp.ndarray | None]]
    type: T.DataType
    dictionary: StringDictionary | None = None  # set when type is varchar
    is_literal: bool = False
    #: set when the result is a pool-backed handle lane (map_keys /
    #: map_values emit a derived ArrayPool over the map pool's buffers)
    pool: object | None = None


def compile_expr(expr: RowExpression, layout: ColumnLayout) -> CompiledExpr:
    return _Compiler(layout).compile(expr)


def _and_valid(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a & b


class _Compiler:
    def __init__(self, layout: ColumnLayout):
        self.layout = layout

    def compile(self, expr: RowExpression) -> CompiledExpr:
        if isinstance(expr, Literal):
            return self._literal(expr)
        if isinstance(expr, InputRef):
            name = expr.name
            return CompiledExpr(
                lambda env: env[name],
                expr.type,
                self.layout.dictionaries.get(name),
            )
        if isinstance(expr, Cast):
            return self._cast(expr)
        if isinstance(expr, Call):
            return self._call(expr)
        raise NotImplementedError(f"cannot compile {expr!r}")

    def _array_fn(self, expr: Call) -> CompiledExpr:
        """Array functions over pool-backed columns: a host LUT sized
        by the pool (lengths / element-at-k / contains-constant) plus
        one device gather by the handle lane — the same compile-time
        shape as dictionary string predicates (the ArrayBlock ops of
        the reference lowered to the pool+handle design)."""
        name = expr.name
        arr = expr.args[0]
        from trino_tpu.page import ArrayPool, MapPool, RowPool

        if isinstance(arr, InputRef):
            pool = self.layout.array_pools.get(arr.name)
            if pool is None:
                raise NotImplementedError(
                    f"{name}: column {arr.name!r} has no array pool"
                )
            a = self.compile(arr)
        elif isinstance(arr, Literal) and arr.value is not None:
            # constant ARRAY[]/MAP()/ROW() literal: _literal builds the
            # one-entry pool + constant handle 0
            a = self.compile(arr)
            pool = a.pool
        else:
            raise NotImplementedError(
                f"{name} over a computed array expression"
            )
        n = max(len(pool), 1)

        if isinstance(pool, RowPool) or name == "row_field":
            return self._row_field(expr, a, pool, n)
        if isinstance(pool, MapPool):
            if name in ("map_keys", "map_values"):
                # a derived ArrayPool sharing the map pool's offsets
                # and one of its flat buffers; handles pass through
                buf = pool.keys if name == "map_keys" else pool.values
                et = (
                    pool.key_type if name == "map_keys"
                    else pool.value_type
                )
                derived = ArrayPool(pool.offsets, buf, et)
                return CompiledExpr(
                    a.fn, T.ArrayType(et), pool=derived
                )
            if name == "subscript":
                return self._map_subscript(expr, a, pool, n)
            # cardinality falls through to the shared lengths path
        lens = pool.lengths()
        if name == "cardinality":
            table = jnp.asarray(
                np.pad(lens, (0, n - len(lens))).astype(np.int64)
            )

            def ev_card(env):
                h, v = a.fn(env)
                return table[jnp.clip(h, 0, n - 1)], v

            return CompiledExpr(ev_card, T.BIGINT)
        if name == "subscript":
            idx = expr.args[1]
            if not isinstance(idx, Literal) or idx.value is None:
                raise NotImplementedError(
                    "array subscript index must be a constant"
                )
            k = int(idx.value)
            ok_h = (lens >= k) & (k >= 1)
            at = np.where(ok_h, pool.offsets[:-1] + (k - 1), 0)
            vals = pool.values[np.clip(at, 0, max(len(pool.values) - 1, 0))] \
                if len(pool.values) else np.zeros(len(lens), dtype=np.int64)
            et = expr.type
            out_dict = None
            if isinstance(et, T.VarcharType):
                out_dict, codes = StringDictionary.from_strings(
                    vals.astype(str) if len(vals) else np.asarray([], str)
                )
                vals = codes
            tbl = jnp.asarray(np.pad(
                np.asarray(vals, dtype=et.np_dtype), (0, n - len(lens))
            ))
            okt = jnp.asarray(np.pad(ok_h, (0, n - len(lens))))

            def ev_sub(env):
                h, v = a.fn(env)
                hc = jnp.clip(h, 0, n - 1)
                ok = okt[hc] if v is None else (okt[hc] & v)
                return tbl[hc], ok

            return CompiledExpr(ev_sub, et, out_dict)
        # contains(arr, constant)
        needle = expr.args[1]
        if not isinstance(needle, Literal) or needle.value is None:
            raise NotImplementedError(
                "contains() needle must be a constant"
            )
        want = _literal_device_value(needle)
        if len(pool.values) and len(lens):
            # vectorized segmented any: one equality pass + scatter-or
            # by array id (reduceat would mis-segment when trailing
            # arrays are empty: offsets[:-1] may equal len(values))
            eq = pool.values == want
            seg_id = np.repeat(np.arange(len(lens)), lens)
            hit = np.zeros(len(lens), dtype=np.bool_)
            np.logical_or.at(hit, seg_id, eq)
        else:
            hit = np.zeros(len(lens), dtype=np.bool_)
        ht = jnp.asarray(np.pad(hit, (0, n - len(lens))))

        def ev_contains(env):
            h, v = a.fn(env)
            return ht[jnp.clip(h, 0, n - 1)], v

        return CompiledExpr(ev_contains, T.BOOLEAN)

    def _map_subscript(self, expr: Call, a, pool, n: int) -> CompiledExpr:
        """map[key] / element_at(map, key) with a constant key: a host
        LUT (value-at-key per map, presence mask) + one device gather
        (MapSubscriptOperator / MapElementAt lowered to pool+handle;
        absent keys yield NULL)."""
        key = expr.args[1]
        if not isinstance(key, Literal) or key.value is None:
            raise NotImplementedError("map key must be a constant")
        want = _literal_device_value(key)
        lens = pool.lengths()
        m = len(lens)
        if len(pool.keys) and m:
            eq = pool.keys == want
            # scatter-min by map id (reduceat would mis-segment when
            # trailing maps are empty: offsets[:-1] may equal len(keys))
            map_id = np.repeat(np.arange(m), lens)
            pos = np.where(eq, np.arange(len(eq)), len(eq))
            first = np.full(m, len(eq), dtype=np.int64)
            np.minimum.at(first, map_id, pos)
            ok_h = first < len(eq)
            at = np.where(ok_h, first, 0)
            vals = pool.values[at]
        else:
            ok_h = np.zeros(m, dtype=np.bool_)
            vals = np.zeros(m, dtype=np.int64)
        et = expr.type
        out_dict = None
        if vals.dtype == object:
            # NULL map values ride object buffers: clear validity and
            # fill with the type's zero so the fixed-width cast succeeds
            nn = np.asarray([v is not None for v in vals], dtype=np.bool_)
            ok_h = ok_h & nn
            fill = "" if isinstance(et, T.VarcharType) else 0
            vals = np.asarray(
                [fill if v is None else v for v in vals], dtype=object
            )
        if isinstance(et, T.VarcharType):
            out_dict, codes = StringDictionary.from_strings(
                vals.astype(str) if len(vals) else np.asarray([], str)
            )
            vals = codes
        tbl = jnp.asarray(np.pad(
            np.asarray(vals, dtype=et.np_dtype), (0, n - m)
        ))
        okt = jnp.asarray(np.pad(ok_h, (0, n - m)))

        def ev(env):
            h, v = a.fn(env)
            hc = jnp.clip(h, 0, n - 1)
            ok = okt[hc] if v is None else (okt[hc] & v)
            return tbl[hc], ok

        return CompiledExpr(ev, et, out_dict)

    def _row_field(self, expr: Call, a, pool, n: int) -> CompiledExpr:
        """row[ordinal] / row.name: the field's pool column is itself
        the LUT — one device gather by handle (RowBlock field access)."""
        idx = expr.args[1]
        if not isinstance(idx, Literal) or idx.value is None:
            raise NotImplementedError("row field index must be constant")
        fi = int(idx.value)
        vals, fvalid = pool.fields[fi]
        et = expr.type
        out_dict = None
        if isinstance(et, T.VarcharType):
            out_dict, codes = StringDictionary.from_strings(
                vals.astype(str) if len(vals) else np.asarray([], str)
            )
            vals = codes
        m = len(vals)
        tbl = jnp.asarray(np.pad(
            np.asarray(vals, dtype=et.np_dtype), (0, n - m)
        ))
        okt = None
        if fvalid is not None:
            okt = jnp.asarray(np.pad(fvalid, (0, n - m)))

        def ev(env):
            h, v = a.fn(env)
            hc = jnp.clip(h, 0, n - 1)
            ok = v
            if okt is not None:
                ok = okt[hc] if v is None else (okt[hc] & v)
            return tbl[hc], ok

        return CompiledExpr(ev, et, out_dict)

    # ---- literals --------------------------------------------------------
    def _literal(self, expr: Literal) -> CompiledExpr:
        if expr.value is None:
            dtype = expr.type.np_dtype
            return CompiledExpr(
                lambda env: (
                    jnp.zeros((), dtype=dtype),
                    jnp.zeros((), dtype=jnp.bool_),
                ),
                expr.type,
                is_literal=True,
            )
        if isinstance(expr.type, (T.ArrayType, T.MapType, T.RowType)):
            # a one-entry pool + constant handle 0 (the ValuesNode
            # single-row constant form of pool-backed columns)
            from trino_tpu.page import ArrayPool, MapPool, RowPool

            t = expr.type
            if isinstance(t, T.MapType):
                pool, _h = MapPool.from_pymaps(
                    [list(expr.value)], t.key, t.value
                )
            elif isinstance(t, T.RowType):
                pool, _h = RowPool.from_pytuples([expr.value], t)
            else:
                pool, _h = ArrayPool.from_pylists(
                    [list(expr.value)], t.element
                )
            return CompiledExpr(
                lambda env: (jnp.zeros((), dtype=jnp.int32), None),
                t, is_literal=True, pool=pool,
            )
        if isinstance(expr.type, T.VarcharType):
            d = StringDictionary(np.asarray([str(expr.value)]))
            return CompiledExpr(
                lambda env: (jnp.zeros((), dtype=jnp.int32), None),
                expr.type,
                d,
                is_literal=True,
            )
        value = _literal_device_value(expr)
        dtype = expr.type.np_dtype
        return CompiledExpr(
            lambda env: (jnp.asarray(value, dtype=dtype), None),
            expr.type,
            is_literal=True,
        )

    # ---- casts -----------------------------------------------------------
    def _cast(self, expr: Cast) -> CompiledExpr:
        src = self.compile(expr.arg)
        s_t, d_t = src.type, expr.type
        if s_t == d_t:
            return src

        def wrap(f):
            def ev(env):
                data, valid = src.fn(env)
                return f(data), valid

            # a cast of a literal is still a literal (NULL literals in
            # CASE branches arrive here wrapped in a coercion Cast)
            return CompiledExpr(ev, d_t, is_literal=src.is_literal)

        if isinstance(d_t, T.DoubleType) or isinstance(d_t, T.RealType):
            dtype = d_t.np_dtype
            if isinstance(s_t, T.DecimalType) and s_t.is_long:
                # two-limb -> double: hi*2^32 + lo, then unscale
                # (float64 approximation; exactness lives in the limb
                # aggregates, not in mixed arithmetic)
                scale = 10.0 ** s_t.scale

                def limbs_to_double(x):
                    # a site of its own in a device trace (the scope
                    # grammar of exec/kernels.py): float64 is emulated
                    # on the chip, and a HAVING over a decimal(38) sum
                    # pays this a group
                    with jax.named_scope("s:limbs_to_double"):
                        return (
                            x[..., 0].astype(jnp.float64) * 4294967296.0
                            + x[..., 1].astype(jnp.float64)
                        ).astype(dtype) / scale

                return wrap(limbs_to_double)
            if isinstance(s_t, T.DecimalType):
                scale = 10.0 ** s_t.scale
                return wrap(lambda x: x.astype(dtype) / scale)
            return wrap(lambda x: x.astype(dtype))
        if isinstance(d_t, T.DecimalType):
            if (isinstance(s_t, T.DecimalType) and s_t.is_long) or (
                isinstance(s_t, T.DecimalType) and d_t.is_long
            ):
                return self._limb_rescale_cast(src, s_t, d_t)
            if d_t.is_long and s_t.is_integer:
                from trino_tpu.exec.aggregates import _limb_encode

                m = 10 ** d_t.scale
                return wrap(
                    lambda x: _limb_encode(x.astype(jnp.int64) * m)
                )
            if d_t.is_long and isinstance(s_t, (T.DoubleType, T.RealType)):
                # double -> decimal(>18): scale, round half away from
                # zero, split into limbs (float64 carries ~15-16
                # significant digits; beyond that the reference's
                # Int128 exactness is unattainable from a double too)
                m = 10.0 ** d_t.scale

                def ev_f2l(env, _m=m):
                    x, v = src.fn(env)
                    y = jnp.sign(x) * jnp.floor(jnp.abs(x) * _m + 0.5)
                    hi = jnp.floor(y / 4294967296.0)
                    lo = (y - hi * 4294967296.0).astype(jnp.int64)
                    return jnp.stack(
                        [hi.astype(jnp.int64), lo], axis=-1
                    ), v

                return CompiledExpr(ev_f2l, d_t, is_literal=src.is_literal)
            if d_t.is_long:
                raise NotImplementedError(f"cast {s_t} -> {d_t}")
            if isinstance(s_t, T.DecimalType):
                if d_t.scale >= s_t.scale:
                    m = 10 ** (d_t.scale - s_t.scale)
                    return wrap(lambda x: x * m)
                m = 10 ** (s_t.scale - d_t.scale)
                return wrap(lambda x: _div_round_half_up(x, m))
            if s_t.is_integer:
                m = 10 ** d_t.scale
                return wrap(lambda x: x.astype(jnp.int64) * m)
            if isinstance(s_t, (T.DoubleType, T.RealType)):
                # round half away from zero (reference: double->decimal
                # cast uses HALF_UP)
                m = 10.0 ** d_t.scale
                return wrap(
                    lambda x: (
                        jnp.sign(x) * jnp.floor(jnp.abs(x) * m + 0.5)
                    ).astype(jnp.int64)
                )
        if d_t.is_integer:
            dtype = d_t.np_dtype
            if isinstance(s_t, T.DecimalType):
                m = 10 ** s_t.scale
                return wrap(lambda x: _div_round_half_up(x, m).astype(dtype))
            if isinstance(s_t, (T.DoubleType, T.RealType)):
                # reference rounds (Math.round): floor(x + 0.5)
                return wrap(lambda x: jnp.floor(x + 0.5).astype(dtype))
            return wrap(lambda x: x.astype(dtype))
        if isinstance(d_t, T.DateType) and isinstance(s_t, T.VarcharType):
            # host-parse the dictionary once -> device gather by code;
            # unparseable values become NULL (reference: cast raises;
            # vectorized execution masks instead)
            if src.dictionary is None:
                raise NotImplementedError(
                    "cast varchar -> date requires a dictionary input"
                )
            vals, bad = [], []
            for v in src.dictionary.values:
                try:
                    vals.append(T.parse_date(str(v)))
                    bad.append(False)
                except (ValueError, TypeError):
                    vals.append(0)
                    bad.append(True)
            n = max(len(vals), 1)
            table = jnp.asarray(np.asarray(
                vals + [0] * (n - len(vals)), dtype=np.int32
            ))
            badt = jnp.asarray(np.asarray(
                bad + [True] * (n - len(bad)), dtype=np.bool_
            ))
            has_bad = any(bad)

            def ev_vc_date(env):
                data, valid = src.fn(env)
                code = jnp.clip(data, 0, n - 1)
                out = table[code]
                if has_bad:
                    okv = ~badt[code]
                    valid = okv if valid is None else (valid & okv)
                return out, valid

            return CompiledExpr(ev_vc_date, d_t, is_literal=src.is_literal)
        if isinstance(d_t, T.DateType) and isinstance(s_t, T.TimestampType):
            return wrap(
                lambda x: (x // T.MICROS_PER_DAY).astype(jnp.int32)
            )
        if isinstance(d_t, T.TimestampType) and isinstance(s_t, T.DateType):
            return wrap(
                lambda x: x.astype(jnp.int64) * T.MICROS_PER_DAY
            )
        if isinstance(d_t, T.VarcharType):
            raise NotImplementedError(f"cast {s_t} -> varchar not yet supported")
        raise NotImplementedError(f"cast {s_t} -> {d_t}")

    def _limb_rescale_cast(
        self, src: CompiledExpr, s_t: "T.DecimalType", d_t: "T.DecimalType"
    ) -> CompiledExpr:
        """Exact decimal rescale where either side is a two-limb
        decimal(>18): upscale multiplies limbs with carry
        normalization, downscale divides 96/64 rounding half away from
        zero (reference: SPI/type/Decimals.rescale over Int128)."""
        from trino_tpu.exec.aggregates import (
            _limb_div_round,
            _limb_encode,
            _limb_norm,
        )

        diff = d_t.scale - s_t.scale
        if 10 ** abs(diff) > 2**31:
            raise NotImplementedError(
                f"cast {s_t} -> {d_t}: rescale by >10^9"
            )
        s_long = s_t.is_long

        def ev(env):
            x, v = src.fn(env)
            if s_long:
                hi, lo = x[..., 0], x[..., 1]
            else:
                xi = x.astype(jnp.int64)
                hi, lo = xi >> jnp.int64(32), xi & jnp.int64(0xFFFFFFFF)
            if diff > 0:
                m = 10 ** diff
                hi, lo = _limb_norm(hi * m, lo * m)
            elif diff < 0:
                q = _limb_div_round(hi, lo, jnp.int64(10 ** (-diff)))
                if d_t.is_long:
                    return _limb_encode(q), v
                return q, v
            if d_t.is_long:
                return jnp.stack([hi, lo], axis=-1), v
            return hi * jnp.int64(4294967296) + lo, v

        return CompiledExpr(ev, d_t, is_literal=src.is_literal)

    # ---- calls -----------------------------------------------------------
    def _call(self, expr: Call) -> CompiledExpr:
        name = expr.name
        if name in ("and", "or"):
            return self._logic(expr)
        if name == "not":
            a = self.compile(expr.args[0])
            return CompiledExpr(
                lambda env: (lambda d, v: (~d, v))(*a.fn(env)), T.BOOLEAN
            )
        if name == "is_null":
            a = self.compile(expr.args[0])

            def ev_isnull(env):
                data, valid = a.fn(env)
                if valid is None:
                    return jnp.zeros(jnp.shape(data), dtype=jnp.bool_), None
                return ~valid, None

            return CompiledExpr(ev_isnull, T.BOOLEAN)
        if name == "if":
            return self._if(expr)
        if name == "coalesce":
            return self._coalesce(expr)
        if name == "in":
            return self._in(expr)
        if name in (
            "cardinality", "subscript", "contains",
            "map_keys", "map_values", "row_field",
        ):
            return self._array_fn(expr)
        if name in _STRING_PREDICATES:
            return self._string_predicate(expr)
        if name in _STRING_TRANSFORMS:
            return self._string_transform(expr)
        if name in _DICT_VALUE_FNS:
            return self._dict_value_fn(expr)
        if name == "nullif":
            a = self.compile(expr.args[0])
            cond = self.compile(expr.args[1])

            def ev_nullif(env, _a=a, _c=cond):
                d, v = _a.fn(env)
                cd, cv = _c.fn(env)
                # nullify only where the comparison is TRUE (an unknown
                # comparison keeps ``a`` — reference NullIf semantics)
                nullify = cd if cv is None else (cd & cv)
                nv = ~nullify if v is None else (v & ~nullify)
                return d, nv

            return CompiledExpr(ev_nullif, expr.type, a.dictionary)
        if name in ("eq", "ne", "lt", "le", "gt", "ge"):
            return self._comparison(expr)
        if name in ("add", "subtract", "multiply", "divide", "modulus"):
            return self._arith(expr)
        if name == "negate":
            a = self.compile(expr.args[0])
            return CompiledExpr(
                lambda env: (lambda d, v: (-d, v))(*a.fn(env)), expr.type
            )
        if name == "concat_cols":
            return self._concat_cols(expr)
        if name == "round":
            return self._round(expr)
        if name in _SIMPLE_FNS:
            return self._simple(expr)
        raise NotImplementedError(f"function {name} not implemented")

    def _concat_cols(self, expr: Call) -> CompiledExpr:
        """varchar || varchar between two dictionary-backed columns:
        the result dictionary is the (bounded) cross product of the
        operand dictionaries; the device op is one gather by the
        composite code a*|B| + b (the ConcatFunction analog under the
        dictionary-encode-early design)."""
        a = self.compile(expr.args[0])
        b = self.compile(expr.args[1])
        da, db = a.dictionary, b.dictionary
        if da is None or db is None:
            raise NotImplementedError(
                "|| requires dictionary-backed varchar operands"
            )
        na, nb = max(len(da), 1), max(len(db), 1)
        if na * nb > 4_000_000:
            raise NotImplementedError(
                f"|| dictionary product too large ({na}x{nb})"
            )
        pairs = np.asarray(
            [str(x) + str(y) for x in da.values for y in db.values]
            or [""],
            dtype=object,
        )
        new_dict, codes = StringDictionary.from_strings(pairs)
        remap = jnp.asarray(codes.astype(np.int32))

        def ev(env):
            ad, av = a.fn(env)
            bd, bv = b.fn(env)
            code = jnp.clip(
                ad.astype(jnp.int32) * nb + bd.astype(jnp.int32),
                0, na * nb - 1,
            )
            return jnp.take(remap, code, mode="clip"), _and_valid(av, bv)

        return CompiledExpr(ev, T.VARCHAR, new_dict)

    def _round(self, expr: Call) -> CompiledExpr:
        """round(x[, n]): half away from zero (reference
        MathFunctions.round — NOT banker's rounding). Decimal inputs
        round on the unscaled integer; the digit count must be a
        constant (it shapes the compiled program)."""
        a = self.compile(expr.args[0])
        ndig = 0
        if len(expr.args) > 1:
            d = expr.args[1]
            if not isinstance(d, Literal) or d.value is None:
                raise NotImplementedError(
                    "round() digit count must be a constant"
                )
            ndig = int(d.value)
        out_t = expr.type

        def ev(env):
            x, v = a.fn(env)
            if isinstance(a.type, T.DecimalType):
                s = a.type.scale
                if ndig >= s:
                    return x, v
                m = 10 ** (s - ndig)
                return _div_round_half_up(x, m) * m, v
            if a.type.is_integer:
                return x, v
            scale = jnp.asarray(10.0 ** ndig, dtype=x.dtype)
            y = x * scale
            return (
                jnp.sign(y) * jnp.floor(jnp.abs(y) + 0.5) / scale
            ).astype(out_t.np_dtype), v

        return CompiledExpr(ev, out_t)

    def _logic(self, expr: Call) -> CompiledExpr:
        parts = [self.compile(a) for a in expr.args]
        is_and = expr.name == "and"

        def ev(env):
            datas, valids = zip(*(p.fn(env) for p in parts))
            # Kleene: fill nulls with the identity, track "known" rows
            ident = True if is_and else False
            filled = [
                d if v is None else jnp.where(v, d, ident)
                for d, v in zip(datas, valids)
            ]
            out = filled[0]
            for f in filled[1:]:
                out = (out & f) if is_and else (out | f)
            if all(v is None for v in valids):
                return out, None
            # null unless every input known, or the result is decided
            known = None
            for v in valids:
                known = _and_valid(known, v)
            decided = out != ident  # AND: any false decides; OR: any true
            return out, known | decided if known is not None else None

        return CompiledExpr(ev, T.BOOLEAN)

    def _if(self, expr: Call) -> CompiledExpr:
        cond, then, els = (self.compile(a) for a in expr.args)
        out_dict = _merge_result_dicts(expr.type, [then, els])
        redict_then = _redict_fn(then, out_dict)
        redict_els = _redict_fn(els, out_dict)

        def ev(env):
            c_d, c_v = cond.fn(env)
            t_d, t_v = then.fn(env)
            e_d, e_v = els.fn(env)
            take_then = c_d if c_v is None else (c_d & c_v)
            data = jnp.where(take_then, redict_then(t_d), redict_els(e_d))
            if t_v is None and e_v is None:
                return data, None
            t_vv = t_v if t_v is not None else jnp.ones_like(take_then)
            e_vv = e_v if e_v is not None else jnp.ones_like(take_then)
            return data, jnp.where(take_then, t_vv, e_vv)

        return CompiledExpr(ev, expr.type, out_dict)

    def _coalesce(self, expr: Call) -> CompiledExpr:
        parts = [self.compile(a) for a in expr.args]
        out_dict = _merge_result_dicts(expr.type, parts)
        redicts = [_redict_fn(p, out_dict) for p in parts]

        def ev(env):
            data, valid = parts[0].fn(env)
            data = redicts[0](data)
            for p, rd in zip(parts[1:], redicts[1:]):
                if valid is None:
                    break
                d, v = p.fn(env)
                data = jnp.where(valid, data, rd(d))
                valid = valid | (v if v is not None else True)
            return data, valid

        return CompiledExpr(ev, expr.type, out_dict)

    def _in(self, expr: Call) -> CompiledExpr:
        value = expr.args[0]
        items = expr.args[1:]
        a = self.compile(value)
        if isinstance(value.type, T.VarcharType):
            # IN over literal strings -> dictionary LUT
            dict_ = a.dictionary
            if dict_ is None or not all(isinstance(i, Literal) for i in items):
                raise NotImplementedError("varchar IN requires literal list")
            wanted = {str(i.value) for i in items}
            lut = np.isin(dict_.values, list(wanted))
            lut_dev = jnp.asarray(lut) if len(lut) else jnp.zeros(1, dtype=jnp.bool_)

            def ev_str(env):
                data, valid = a.fn(env)
                return jnp.take(lut_dev, data, mode="clip"), valid

            return CompiledExpr(ev_str, T.BOOLEAN)
        compiled_items = [self.compile(i) for i in items]

        def ev(env):
            data, valid = a.fn(env)
            out = None
            any_null_item = None
            for ci in compiled_items:
                d, v = ci.fn(env)
                hit = data == d
                if v is not None:
                    hit = hit & v
                    item_null = ~v
                    any_null_item = (
                        item_null if any_null_item is None else any_null_item | item_null
                    )
                out = hit if out is None else out | hit
            if any_null_item is not None:
                # 3VL: no match + a NULL item -> NULL, not FALSE
                valid = _and_valid(valid, out | ~any_null_item)
            return out, valid

        return CompiledExpr(ev, T.BOOLEAN)

    def _comparison(self, expr: Call) -> CompiledExpr:
        lhs, rhs = expr.args
        a = self.compile(lhs)
        b = self.compile(rhs)
        if isinstance(lhs.type, T.VarcharType) or isinstance(rhs.type, T.VarcharType):
            return self._string_comparison(expr, a, b)
        a_long = isinstance(lhs.type, T.DecimalType) and lhs.type.is_long
        b_long = isinstance(rhs.type, T.DecimalType) and rhs.type.is_long
        if a_long or b_long:
            return self._limb_comparison(expr, a, b, a_long, b_long)
        if (
            isinstance(lhs.type, T.DecimalType)
            and isinstance(rhs.type, T.DecimalType)
            and lhs.type.scale != rhs.type.scale
        ):
            return self._mixed_scale_comparison(expr, a, b)
        op = _CMP_OPS[expr.name]

        def ev(env):
            a_d, a_v = a.fn(env)
            b_d, b_v = b.fn(env)
            return op(a_d, b_d), _and_valid(a_v, b_v)

        return CompiledExpr(ev, T.BOOLEAN)

    def _limb_comparison(
        self, expr: Call, a: CompiledExpr, b: CompiledExpr,
        a_long: bool, b_long: bool,
    ) -> CompiledExpr:
        """Exact comparison on two-limb decimals: numeric order equals
        lexicographic (hi, lo) order (lo canonical non-negative). Both
        sides must share the scale (analyzer coerces mixed-scale long
        comparisons through DOUBLE)."""
        if (a_long and b_long) and a.type.scale != b.type.scale:
            raise NotImplementedError(
                "mixed-scale long-decimal comparison"
            )
        if a_long != b_long:
            # widen the short side to limbs (same scale required)
            if a.type.scale != b.type.scale:
                raise NotImplementedError(
                    "mixed-scale long/short decimal comparison"
                )
        name = expr.name

        def limbs(c, is_long):
            def get(env):
                d, v = c.fn(env)
                if is_long:
                    return d[..., 0], d[..., 1], v
                return d >> jnp.int64(32), d & jnp.int64(0xFFFFFFFF), v

            return get

        ga = limbs(a, a_long)
        gb = limbs(b, b_long)

        def ev(env):
            ah, al, av = ga(env)
            bh, bl, bv = gb(env)
            if name == "eq":
                out = (ah == bh) & (al == bl)
            elif name == "ne":
                out = (ah != bh) | (al != bl)
            elif name == "lt":
                out = (ah < bh) | ((ah == bh) & (al < bl))
            elif name == "le":
                out = (ah < bh) | ((ah == bh) & (al <= bl))
            elif name == "gt":
                out = (ah > bh) | ((ah == bh) & (al > bl))
            else:  # ge
                out = (ah > bh) | ((ah == bh) & (al >= bl))
            return out, _and_valid(av, bv)

        return CompiledExpr(ev, T.BOOLEAN)

    def _mixed_scale_comparison(self, expr: Call, a: CompiledExpr, b: CompiledExpr) -> CompiledExpr:
        """Exact decimal comparison across scales without rescaling.

        Upscaling the coarse side by 10^(s_b - s_a) overflows int64 for
        large values (the reference sidesteps this with Int128 math,
        SPI/type/Decimals.java). Instead compare at the coarser scale:
        with m = 10^(s_b - s_a), q = floor(b / m), r = b - q*m (r >= 0):
        a*m <=> q*m + r reduces to comparing (a, 0) with (q, r)
        lexicographically.
        """
        name = expr.name
        if a.type.scale > b.type.scale:
            return self._mixed_scale_comparison(
                Call(
                    T.BOOLEAN,
                    _MIRRORED_CMP.get(name, name),
                    (expr.args[1], expr.args[0]),
                ),
                b, a,
            )
        m = 10 ** (b.type.scale - a.type.scale)

        def ev(env):
            a_d, a_v = a.fn(env)
            b_d, b_v = b.fn(env)
            q = b_d // m  # floor division: r in [0, m)
            r = b_d - q * m
            if name == "eq":
                out = (a_d == q) & (r == 0)
            elif name == "ne":
                out = (a_d != q) | (r != 0)
            elif name == "lt":
                out = (a_d < q) | ((a_d == q) & (r > 0))
            elif name == "le":
                out = a_d <= q
            elif name == "gt":
                out = a_d > q
            else:  # ge
                out = (a_d > q) | ((a_d == q) & (r == 0))
            return out, _and_valid(a_v, b_v)

        return CompiledExpr(ev, T.BOOLEAN)

    def _string_comparison(self, expr: Call, a: CompiledExpr, b: CompiledExpr) -> CompiledExpr:
        op = _CMP_OPS[expr.name]
        if a.is_literal and not b.is_literal:
            # normalize literal to the rhs with the mirrored operator
            name = _MIRRORED_CMP.get(expr.name, expr.name)
            return self._string_comparison(
                Call(T.BOOLEAN, name, (expr.args[1], expr.args[0])), b, a
            )
        # literal rhs: translate to a code comparison against the
        # column's dictionary (codes are in lexicographic order)
        if a.dictionary is not None and b.dictionary is not None:
            if b.is_literal:
                s = str(b.dictionary.values[0])
                code, exact = _code_bound(a.dictionary, s)

                # when the literal is absent, `code` is the insertion
                # point: x < s  <=>  x <= s  <=>  code(x) < code, and
                # x > s  <=>  x >= s  <=>  code(x) >= code
                name = expr.name
                if not exact:
                    name = {"le": "lt", "gt": "ge"}.get(name, name)

                def ev_lit(env):
                    a_d, a_v = a.fn(env)
                    if name == "eq":
                        r = (a_d == code) if exact else jnp.zeros_like(a_d, dtype=jnp.bool_)
                    elif name == "ne":
                        r = (a_d != code) if exact else jnp.ones_like(a_d, dtype=jnp.bool_)
                    else:
                        r = _CMP_OPS[name](a_d, jnp.asarray(code, dtype=a_d.dtype))
                    return r, a_v

                return CompiledExpr(ev_lit, T.BOOLEAN)
            if a.dictionary is b.dictionary:
                def ev_shared(env):
                    a_d, a_v = a.fn(env)
                    b_d, b_v = b.fn(env)
                    return op(a_d, b_d), _and_valid(a_v, b_v)

                return CompiledExpr(ev_shared, T.BOOLEAN)
            # distinct dictionaries: remap both onto their union at
            # compile time (codes stay order-preserving), compare codes
            merged, remap_a, remap_b = a.dictionary.union(b.dictionary)
            ra = _remap_gather(remap_a)
            rb = _remap_gather(remap_b)

            def ev_merged(env):
                a_d, a_v = a.fn(env)
                b_d, b_v = b.fn(env)
                return op(ra(a_d), rb(b_d)), _and_valid(a_v, b_v)

            return CompiledExpr(ev_merged, T.BOOLEAN)
        raise NotImplementedError(
            "varchar comparison requires a literal or a shared dictionary"
        )

    def _string_predicate(self, expr: Call) -> CompiledExpr:
        """LIKE & friends: host-eval over the dictionary -> device LUT."""
        a = self.compile(expr.args[0])
        if a.dictionary is None:
            raise NotImplementedError(f"{expr.name} requires a dictionary input")
        pattern = str(expr.args[1].value)  # type: ignore[attr-defined]
        if expr.name in ("like", "not_like"):
            rx = re.compile(_like_to_regex(pattern), re.DOTALL)
            matcher = rx.fullmatch
        elif expr.name == "regexp_like":
            # Trino regexp_like is a SEARCH (substring match), not a
            # full match (JoniRegexpFunctions.regexpLike)
            matcher = re.compile(pattern).search
        else:
            raise NotImplementedError(expr.name)
        lut = np.fromiter(
            (matcher(str(v)) is not None for v in a.dictionary.values),
            dtype=np.bool_,
            count=len(a.dictionary),
        )
        if expr.name == "not_like":
            lut = ~lut
        lut_dev = jnp.asarray(lut) if len(lut) else jnp.zeros(1, dtype=jnp.bool_)

        def ev(env):
            data, valid = a.fn(env)
            return jnp.take(lut_dev, data, mode="clip"), valid

        return CompiledExpr(ev, T.BOOLEAN)

    def _string_transform(self, expr: Call) -> CompiledExpr:
        """substr/lower/upper/...: transform dictionary values on host,
        re-sort, and compile to a device code-remap gather."""
        a = self.compile(expr.args[0])
        if a.dictionary is None:
            raise NotImplementedError(f"{expr.name} requires a dictionary input")
        f = _STRING_TRANSFORMS[expr.name]
        lits = [l.value for l in expr.args[1:]]  # type: ignore[attr-defined]
        try:
            raw = [f(str(v), *lits) for v in a.dictionary.values]
        except (re.error, IndexError) as e:
            raise ValueError(f"{expr.name}: {e}") from e
        # a transform may return None per value (regexp_extract with no
        # match is NULL, Trino semantics): carry a per-code null LUT
        null_lut = np.fromiter(
            (v is None for v in raw), dtype=np.bool_, count=len(raw)
        )
        transformed = np.asarray(
            ["" if v is None else v for v in raw], dtype=object
        )
        if len(transformed):
            new_dict, codes = StringDictionary.from_strings(transformed)
            remap = jnp.asarray(codes)
        else:
            new_dict, remap = StringDictionary(np.asarray([], dtype=object)), jnp.zeros(
                1, dtype=jnp.int32
            )
        has_nulls = bool(null_lut.any())
        null_dev = (
            jnp.asarray(null_lut) if has_nulls and len(null_lut)
            else None
        )

        def ev(env):
            data, valid = a.fn(env)
            out = jnp.take(remap, data, mode="clip")
            if null_dev is not None:
                notnull = ~jnp.take(null_dev, data, mode="clip")
                valid = notnull if valid is None else (valid & notnull)
            return out, valid

        return CompiledExpr(ev, expr.type, new_dict)

    def _dict_value_fn(self, expr: Call) -> CompiledExpr:
        """length/strpos/starts_with: evaluate per dictionary value on
        host, gather the result by code on device."""
        a = self.compile(expr.args[0])
        if a.dictionary is None:
            raise NotImplementedError(f"{expr.name} requires a dictionary input")
        f = _DICT_VALUE_FNS[expr.name]
        lits = [l.value for l in expr.args[1:]]  # type: ignore[attr-defined]
        out_dtype = expr.type.np_dtype
        table = np.asarray(
            [f(str(v), *lits) for v in a.dictionary.values],
            dtype=out_dtype,
        )
        if not len(table):
            table = np.zeros(1, dtype=out_dtype)
        dev_table = jnp.asarray(table)

        def ev(env):
            data, valid = a.fn(env)
            return jnp.take(dev_table, data, mode="clip"), valid

        return CompiledExpr(ev, expr.type)

    def _arith(self, expr: Call) -> CompiledExpr:
        lhs, rhs = expr.args
        a = self.compile(lhs)
        b = self.compile(rhs)
        name = expr.name
        out_t = expr.type

        if isinstance(out_t, T.DecimalType):
            return self._decimal_arith(expr, a, b)

        ops = {
            "add": jnp.add,
            "subtract": jnp.subtract,
            "multiply": jnp.multiply,
        }
        if name in ops:
            op = ops[name]

            def ev(env):
                a_d, a_v = a.fn(env)
                b_d, b_v = b.fn(env)
                return op(a_d, b_d).astype(out_t.np_dtype), _and_valid(a_v, b_v)

            return CompiledExpr(ev, out_t)
        if name == "divide":
            if out_t.is_integer:
                def ev_idiv(env):
                    a_d, a_v = a.fn(env)
                    b_d, b_v = b.fn(env)
                    safe = jnp.where(b_d == 0, 1, b_d)
                    q = _int_div_trunc(a_d, safe)
                    # division by zero nulls the row (the reference
                    # raises DIVISION_BY_ZERO; vectorized execution
                    # cannot raise per-row — masked at output instead)
                    return q.astype(out_t.np_dtype), _and_valid(
                        _and_valid(a_v, b_v), b_d != 0
                    )

                return CompiledExpr(ev_idiv, out_t)

            def ev_fdiv(env):
                a_d, a_v = a.fn(env)
                b_d, b_v = b.fn(env)
                return (a_d / b_d).astype(out_t.np_dtype), _and_valid(a_v, b_v)

            return CompiledExpr(ev_fdiv, out_t)
        if name == "modulus":
            def ev_mod(env):
                a_d, a_v = a.fn(env)
                b_d, b_v = b.fn(env)
                safe = jnp.where(b_d == 0, 1, b_d)
                r = a_d - _int_div_trunc(a_d, safe) * safe
                return r.astype(out_t.np_dtype), _and_valid(
                    _and_valid(a_v, b_v), b_d != 0
                )

            return CompiledExpr(ev_mod, out_t)
        raise NotImplementedError(name)

    def _decimal_arith(self, expr: Call, a: CompiledExpr, b: CompiledExpr) -> CompiledExpr:
        """Decimal arithmetic on unscaled int64 (reference semantics:
        MAIN/type/DecimalOperators.java — round half-up on divide)."""
        out_t: T.DecimalType = expr.type  # type: ignore[assignment]
        name = expr.name
        s_a = a.type.scale if isinstance(a.type, T.DecimalType) else 0
        s_b = b.type.scale if isinstance(b.type, T.DecimalType) else 0

        def ev(env):
            a_d, a_v = a.fn(env)
            b_d, b_v = b.fn(env)
            valid = _and_valid(a_v, b_v)
            a_i = a_d.astype(jnp.int64)
            b_i = b_d.astype(jnp.int64)
            if name in ("add", "subtract"):
                a_i = a_i * 10 ** (out_t.scale - s_a)
                b_i = b_i * 10 ** (out_t.scale - s_b)
                out = a_i + b_i if name == "add" else a_i - b_i
            elif name == "multiply":
                out = a_i * b_i  # scale s_a + s_b == out_t.scale
            elif name == "divide":
                # rescale so that quotient has out_t.scale
                shift = out_t.scale - s_a + s_b
                num = a_i * 10**shift
                safe = jnp.where(b_i == 0, 1, b_i)
                out = _div_round_half_up(num, safe)
                valid = _and_valid(valid, b_i != 0)  # null the /0 rows
            elif name == "modulus":
                safe = jnp.where(b_i == 0, 1, b_i)
                out = a_i - _int_div_trunc(a_i, safe) * safe
                valid = _and_valid(valid, b_i != 0)
            else:
                raise NotImplementedError(name)
            return out, valid

        return CompiledExpr(ev, out_t)

    def _simple(self, expr: Call) -> CompiledExpr:
        parts = [self.compile(a) for a in expr.args]
        f = _SIMPLE_FNS[expr.name]
        out_t = expr.type

        def ev(env):
            vals = [p.fn(env) for p in parts]
            datas = [d for d, _ in vals]
            valid = None
            for _, v in vals:
                valid = _and_valid(valid, v)
            return f(*datas).astype(out_t.np_dtype), valid

        return CompiledExpr(ev, out_t)


# ---- helpers -------------------------------------------------------------

def _literal_device_value(expr: Literal):
    v = expr.value
    if isinstance(expr.type, T.DateType) and isinstance(v, str):
        return T.parse_date(v)
    if isinstance(expr.type, T.TimestampType) and isinstance(v, str):
        return T.parse_timestamp(v)
    if isinstance(expr.type, T.DecimalType):
        from decimal import Decimal

        return int(
            (Decimal(str(v)) * (10 ** expr.type.scale)).to_integral_value()
        )
    return v


def _int_div_trunc(a, b):
    """C-style truncating integer division (SQL semantics), vs
    python/jnp floor division."""
    q = a // b
    r = a - q * b
    fix = (r != 0) & ((a < 0) != (b < 0))
    return q + jnp.where(fix, 1, 0)


def _div_round_half_up(a, b):
    """Integer divide rounding half away from zero (Trino decimal rule,
    MAIN reference io.trino.spi.type.Decimals.rescale)."""
    sign = jnp.where((a < 0) != (b < 0), -1, 1)
    aa = jnp.abs(a)
    ab = jnp.abs(b)
    return sign * ((aa + ab // 2) // ab)


def _like_to_regex(pattern: str, escape: str | None = None) -> str:
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if escape and ch == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return "".join(out)


def _code_bound(d: StringDictionary, s: str) -> tuple[int, bool]:
    """(code position of s in dictionary, whether s is present).

    For non-equality comparisons the insertion point works as the
    bound: x < s  <=>  code(x) < insertion_point when s absent.
    """
    i = int(np.searchsorted(d.values, s))
    exact = i < len(d.values) and d.values[i] == s
    if not exact and i == len(d.values):
        # all values < s: use a code past the end
        return len(d.values), False
    return i, exact


def _merge_result_dicts(out_type, parts):
    if not isinstance(out_type, T.VarcharType):
        return None
    # a dictionary-less varchar branch is acceptable only as a typed
    # NULL literal (validity always False — e.g. CASE WHEN ... THEN col
    # END with an implicit NULL else): it contributes an empty
    # dictionary. Hash-pool-coded columns also carry no dictionary but
    # are [n,2] code lanes — merging them silently would corrupt, so
    # they keep the loud error.
    if any(p.dictionary is None and not p.is_literal for p in parts):
        raise NotImplementedError(
            "varchar branches must be dictionary-backed"
        )
    empty = StringDictionary(np.asarray([], dtype=object))
    dicts = [p.dictionary if p.dictionary is not None else empty for p in parts]
    merged = dicts[0]
    for d in dicts[1:]:
        if d is not merged:
            merged, _, _ = merged.union(d)
    return merged


def _redict_fn(part: CompiledExpr, merged: StringDictionary | None):
    """Compile-time code remap onto a merged dictionary (device gather)."""
    if merged is None or part.dictionary is merged or part.dictionary is None:
        # dictionary-less parts are typed NULL literals: their codes
        # are never valid, no remap needed
        return lambda data: data
    remap = np.searchsorted(merged.values, part.dictionary.values).astype(np.int32)
    return _remap_gather(remap)


def _remap_gather(remap: np.ndarray):
    if len(remap) == 0:
        return lambda data: data
    remap_dev = jnp.asarray(remap)
    return lambda data: jnp.take(remap_dev, data, mode="clip")


_CMP_OPS = {
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
    "lt": lambda a, b: a < b,
    "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b,
    "ge": lambda a, b: a >= b,
}

#: operator under argument swap: a OP b == b MIRROR(OP) a
_MIRRORED_CMP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}

_STRING_PREDICATES = {"like", "not_like", "regexp_like"}

_STRING_TRANSFORMS: dict[str, Callable] = {
    "substr": lambda s, start, length=None: (
        s[int(start) - 1 : int(start) - 1 + int(length)]
        if length is not None
        else s[int(start) - 1 :]
    ),
    "lower": lambda s: s.lower(),
    "upper": lambda s: s.upper(),
    "trim": lambda s: s.strip(),
    "ltrim": lambda s: s.lstrip(),
    "rtrim": lambda s: s.rstrip(),
    "reverse": lambda s: s[::-1],
    "replace": lambda s, find, repl="": s.replace(find, repl),
    # || with a literal operand (ConcatFunction over dictionary values)
    "concat_suffix": lambda s, suffix: s + str(suffix),
    "concat_prefix": lambda s, prefix: str(prefix) + s,
    # Trino regex semantics (JoniRegexpFunctions): extract returns the
    # group (NULL-as-empty here: dictionary transforms cannot produce
    # NULL) or '' when unmatched; replace substitutes every match
    "regexp_extract": lambda s, pattern, group=0: (
        (lambda m: (m.group(int(group)) or "") if m else None)(
            re.search(str(pattern), s)
        )
    ),
    "regexp_replace": lambda s, pattern, repl="": re.sub(
        str(pattern),
        _java_replacement(
            str(repl), re.compile(str(pattern)).groups
        ),
        s,
    ),
}


def _java_replacement(repl: str, n_groups: int) -> str:
    r"""Java appendReplacement semantics (what Trino's regexp_replace
    uses) -> python re.sub replacement: $N group references backtrack
    to the largest VALID group number ($10 with one group = group 1 +
    literal '0'); backslash escapes the next character literally; the
    output escapes python's own backslash handling."""
    def lit(c: str) -> str:
        return "\\\\" if c == "\\" else c

    out = []
    i = 0
    while i < len(repl):
        c = repl[i]
        if c == "\\" and i + 1 < len(repl):
            out.append(lit(repl[i + 1]))
            i += 2
            continue
        if c == "$":
            j = i + 1
            while j < len(repl) and repl[j].isdigit():
                j += 1
            # backtrack to the largest group number the pattern has
            while j > i + 1 and int(repl[i + 1:j]) > max(n_groups, 0) \
                    and j - (i + 1) > 1:
                j -= 1
            if j > i + 1:
                out.append(f"\\g<{repl[i + 1:j]}>")
                i = j
                continue
        out.append(lit(c))
        i += 1
    return "".join(out)

#: varchar -> numeric/boolean per-dictionary-value functions: evaluate
#: on the (small) dictionary host-side, gather by code on device
_DICT_VALUE_FNS: dict[str, Callable] = {
    "length": lambda s: len(s),
    "strpos": lambda s, sub: s.find(sub) + 1,
    "starts_with": lambda s, p: s.startswith(p),
}


def _extract_civil(days):
    """Vectorized Gregorian calendar decomposition of epoch days
    (days-from-civil inverse, Howard Hinnant's algorithm)."""
    z = days.astype(jnp.int64) + 719_468
    era = z // 146_097  # jnp // is floor division — no truncation offset
    doe = z - era * 146_097
    yoe = (doe - doe // 1460 + doe // 36_524 - doe // 146_096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


def _days_from_civil(y, m, d):
    """Vectorized inverse of _extract_civil: (y, m, d) -> epoch days
    (Howard Hinnant's days_from_civil)."""
    y = y - (m <= 2)
    era = y // 400
    yoe = y - era * 400
    mp = m + jnp.where(m > 2, -3, 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146_097 + doe - 719_468


def _days_in_month(y, m):
    ny = y + (m == 12)
    nm = jnp.where(m == 12, 1, m + 1)
    return _days_from_civil(ny, nm, 1) - _days_from_civil(y, m, 1)


def _iso_dow(days):
    """ISO day-of-week of epoch days: Monday=1..Sunday=7 (epoch day 0,
    1970-01-01, is a Thursday -> 4). Reference: DateTimeFunctions
    dayOfWeekFromDate."""
    return (days.astype(jnp.int64) + 3) % 7 + 1


def _doy(days):
    y, _, _ = _extract_civil(days)
    return days.astype(jnp.int64) - _days_from_civil(y, jnp.int64(1), jnp.int64(1)) + 1


def _iso_week(days):
    """ISO-8601 week of year: the week containing this day's Thursday
    determines the year; weeks start Monday (reference:
    DateTimeFunctions.weekFromDate via ISOChronology weekOfWeekyear)."""
    days = days.astype(jnp.int64)
    thursday = days - (_iso_dow(days) - 4)
    ty, _, _ = _extract_civil(thursday)
    jan1 = _days_from_civil(ty, jnp.int64(1), jnp.int64(1))
    return (thursday - jan1) // 7 + 1


def _add_months_days(days, months):
    """date + n months with end-of-month day clamping (reference:
    DateTimeFunctions.addFieldValueDate -> Joda addMonths semantics)."""
    y, m, d = _extract_civil(days)
    m0 = y * 12 + (m - 1) + months.astype(jnp.int64)
    y2 = m0 // 12
    m2 = m0 - y2 * 12 + 1
    d2 = jnp.minimum(d, _days_in_month(y2, m2))
    return _days_from_civil(y2, m2, d2)


def _months_between(a, b):
    """Full months from date a to date b: the largest n with
    a + n months <= b (sign-symmetric; reference:
    DateTimeFunctions.diffDate('month') -> Joda monthsBetween)."""
    a = a.astype(jnp.int64)
    b = b.astype(jnp.int64)
    ya, ma, _ = _extract_civil(a)
    yb, mb, _ = _extract_civil(b)
    m = (yb * 12 + mb) - (ya * 12 + ma)
    cand = _add_months_days(a, m)
    m = m - jnp.where((m > 0) & (cand > b), 1, 0)
    return m + jnp.where((m < 0) & (cand < b), 1, 0)


def _ts_add_months(x, m):
    x = x.astype(jnp.int64)
    days = x // 86_400_000_000
    tod = x % 86_400_000_000
    return _add_months_days(days, m) * 86_400_000_000 + tod


def _ts_months_between(a, b):
    """Full months between instants: time-of-day participates (Joda
    monthsBetween over instants — a month has not elapsed until the
    end instant reaches start + n months to the microsecond)."""
    a = a.astype(jnp.int64)
    b = b.astype(jnp.int64)
    m = _months_between(a // 86_400_000_000, b // 86_400_000_000)
    cand = _ts_add_months(a, m)
    m = m - jnp.where((m > 0) & (cand > b), 1, 0)
    return m + jnp.where((m < 0) & (cand < b), 1, 0)


def _ts_trunc(unit_micros):
    def f(x):
        x = x.astype(jnp.int64)
        return x - x % unit_micros  # jnp % floors: correct pre-epoch

    return f


def _ts_trunc_civil(date_trunc_fn):
    """Truncate a timestamp through its civil date component."""

    def f(x):
        days = x.astype(jnp.int64) // 86_400_000_000
        return date_trunc_fn(days) * 86_400_000_000

    return f


def _date_trunc_year(d):
    y, _, _ = _extract_civil(d)
    return _days_from_civil(y, jnp.int64(1), jnp.int64(1))


def _date_trunc_quarter(d):
    y, m, _ = _extract_civil(d)
    return _days_from_civil(y, ((m - 1) // 3) * 3 + 1, jnp.int64(1))


def _date_trunc_month(d):
    y, m, _ = _extract_civil(d)
    return _days_from_civil(y, m, jnp.int64(1))


def _date_trunc_week(d):
    return d.astype(jnp.int64) - (_iso_dow(d) - 1)


_SIMPLE_FNS: dict[str, Callable] = {
    "extract_year": lambda d: _extract_civil(d)[0],
    "extract_month": lambda d: _extract_civil(d)[1],
    "extract_day": lambda d: _extract_civil(d)[2],
    "abs": jnp.abs,
    "sqrt": jnp.sqrt,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "exp": jnp.exp,
    "ln": jnp.log,
    "log2": jnp.log2,
    "log10": jnp.log10,
    "power": lambda a, b: jnp.power(
        a.astype(jnp.float64), b.astype(jnp.float64)
    ),
    "cbrt": jnp.cbrt,
    "sign": jnp.sign,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "asin": jnp.arcsin,
    "acos": jnp.arccos,
    "atan": jnp.arctan,
    "degrees": jnp.degrees,
    "radians": jnp.radians,
    # timestamp fields (micros since epoch)
    "extract_hour": lambda x: (x // 3_600_000_000) % 24,
    "extract_minute": lambda x: (x // 60_000_000) % 60,
    "extract_second": lambda x: (x // 1_000_000) % 60,
    # date/time family (reference: MAIN/operator/scalar/
    # DateTimeFunctions.java:73 — civil-calendar decomposition runs
    # vectorized on device, no per-row host work)
    "extract_quarter": lambda d: (_extract_civil(d)[1] - 1) // 3 + 1,
    "extract_day_of_week": _iso_dow,
    "extract_day_of_year": _doy,
    "extract_week": _iso_week,
    "extract_year_of_week": lambda d: _extract_civil(
        d.astype(jnp.int64) - (_iso_dow(d) - 4)
    )[0],
    "last_day_of_month": lambda d: (
        lambda y, m, _d: _days_from_civil(y, m, _days_in_month(y, m))
    )(*_extract_civil(d)),
    "date_trunc_year": _date_trunc_year,
    "date_trunc_quarter": _date_trunc_quarter,
    "date_trunc_month": _date_trunc_month,
    "date_trunc_week": _date_trunc_week,
    "date_trunc_day": lambda d: d,
    "ts_trunc_year": _ts_trunc_civil(_date_trunc_year),
    "ts_trunc_quarter": _ts_trunc_civil(_date_trunc_quarter),
    "ts_trunc_month": _ts_trunc_civil(_date_trunc_month),
    "ts_trunc_week": _ts_trunc_civil(_date_trunc_week),
    "ts_trunc_day": _ts_trunc(86_400_000_000),
    "ts_trunc_hour": _ts_trunc(3_600_000_000),
    "ts_trunc_minute": _ts_trunc(60_000_000),
    "ts_trunc_second": _ts_trunc(1_000_000),
    "add_months": _add_months_days,
    "ts_add_months": _ts_add_months,
    "months_between": _months_between,
    "ts_months_between": _ts_months_between,
}
