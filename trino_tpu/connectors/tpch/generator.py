"""Deterministic, vectorized TPC-H data generation.

The analog of the reference's in-process TPC-H generator connector
(plugin/trino-tpch/.../TpchConnectorFactory.java:38, backed by the
io.trino.tpch dbgen port). Same schema, same distributions and
structural rules (sparse customer keys, per-order line counts,
retail-price formula, return-flag/status date logic), generated as
numpy columns so a scan at any scale factor is a vectorized array
computation, not a row loop.

Not yet bit-identical to dbgen's RNG streams — correctness tests load
*this* data into sqlite so engine results are checked against golden
results over identical inputs. Columns are generated on demand and
cached, so projection pushdown avoids materializing unused text.
"""

from __future__ import annotations

import os
import zlib

import numpy as np

from trino_tpu import types as T
from trino_tpu.connectors.base import TableSchema
from trino_tpu.connectors.tpch import text
from trino_tpu.types import parse_date

__all__ = ["TpchData", "SCHEMAS", "SCHEMA_SF"]

CURRENT_DATE = parse_date("1995-06-17")
MIN_ORDER_DATE = parse_date("1992-01-01")
MAX_ORDER_DATE = parse_date("1998-08-02")

D152 = T.DecimalType(15, 2)

#: canonical TPC-H column prefixes per table
PREFIX = {
    "region": "r_", "nation": "n_", "supplier": "s_", "customer": "c_",
    "part": "p_", "partsupp": "ps_", "orders": "o_", "lineitem": "l_",
}

_BASE_COLUMNS: dict[str, list[tuple[str, T.DataType]]] = {
    "region": [
        ("regionkey", T.BIGINT), ("name", T.VARCHAR), ("comment", T.VARCHAR)],
    "nation": [
        ("nationkey", T.BIGINT), ("name", T.VARCHAR), ("regionkey", T.BIGINT),
        ("comment", T.VARCHAR)],
    "supplier": [
        ("suppkey", T.BIGINT), ("name", T.VARCHAR), ("address", T.VARCHAR),
        ("nationkey", T.BIGINT), ("phone", T.VARCHAR), ("acctbal", D152),
        ("comment", T.VARCHAR)],
    "customer": [
        ("custkey", T.BIGINT), ("name", T.VARCHAR), ("address", T.VARCHAR),
        ("nationkey", T.BIGINT), ("phone", T.VARCHAR), ("acctbal", D152),
        ("mktsegment", T.VARCHAR), ("comment", T.VARCHAR)],
    "part": [
        ("partkey", T.BIGINT), ("name", T.VARCHAR), ("mfgr", T.VARCHAR),
        ("brand", T.VARCHAR), ("type", T.VARCHAR), ("size", T.INTEGER),
        ("container", T.VARCHAR), ("retailprice", D152), ("comment", T.VARCHAR)],
    "partsupp": [
        ("partkey", T.BIGINT), ("suppkey", T.BIGINT), ("availqty", T.INTEGER),
        ("supplycost", D152), ("comment", T.VARCHAR)],
    "orders": [
        ("orderkey", T.BIGINT), ("custkey", T.BIGINT), ("orderstatus", T.VARCHAR),
        ("totalprice", D152), ("orderdate", T.DATE), ("orderpriority", T.VARCHAR),
        ("clerk", T.VARCHAR), ("shippriority", T.INTEGER), ("comment", T.VARCHAR)],
    "lineitem": [
        ("orderkey", T.BIGINT), ("partkey", T.BIGINT), ("suppkey", T.BIGINT),
        ("linenumber", T.INTEGER), ("quantity", D152), ("extendedprice", D152),
        ("discount", D152), ("tax", D152), ("returnflag", T.VARCHAR),
        ("linestatus", T.VARCHAR), ("shipdate", T.DATE), ("commitdate", T.DATE),
        ("receiptdate", T.DATE), ("shipinstruct", T.VARCHAR),
        ("shipmode", T.VARCHAR), ("comment", T.VARCHAR)],
}

#: external schemas use the canonical prefixed names (l_orderkey, ...)
SCHEMAS: dict[str, TableSchema] = {
    t: TableSchema(t, [(PREFIX[t] + c, ty) for c, ty in cols])
    for t, cols in _BASE_COLUMNS.items()
}

#: named schema -> scale factor, mirroring the reference's tpch schemas
SCHEMA_SF = {
    "tiny": 0.01, "sf1": 1.0, "sf5": 5.0, "sf10": 10.0, "sf100": 100.0,
}


def _seed(sf: float, table: str, stream: str) -> list[int]:
    return [zlib.crc32(table.encode()), zlib.crc32(stream.encode()), int(sf * 1000)]


class TpchData:
    """All eight TPC-H tables at one scale factor, columns on demand."""

    def __init__(self, sf: float):
        self.sf = sf
        self._cache: dict[tuple[str, str], np.ndarray] = {}

    # ---- row counts ------------------------------------------------------
    @property
    def n_supplier(self) -> int:
        return max(1, round(10_000 * self.sf))

    @property
    def n_customer(self) -> int:
        return max(1, round(150_000 * self.sf))

    @property
    def n_part(self) -> int:
        return max(1, round(200_000 * self.sf))

    @property
    def n_orders(self) -> int:
        return max(1, round(1_500_000 * self.sf))

    @property
    def n_partsupp(self) -> int:
        return 4 * self.n_part

    def row_count(self, table: str) -> int:
        if table == "lineitem":
            return self.n_lineitem  # from per-order counts, no column materialization
        return {
            "region": 5,
            "nation": 25,
            "supplier": self.n_supplier,
            "customer": self.n_customer,
            "part": self.n_part,
            "partsupp": self.n_partsupp,
            "orders": self.n_orders,
        }[table]

    def _rng(self, table: str, stream: str) -> np.random.Generator:
        return np.random.default_rng(_seed(self.sf, table, stream))

    # ---- public API ------------------------------------------------------
    def column(self, table: str, name: str) -> np.ndarray:
        # accept both canonical prefixed (l_orderkey) and bare names
        prefix = PREFIX.get(table, "")
        if prefix and name.startswith(prefix):
            name = name[len(prefix):]
        key = (table, name)
        if key not in self._cache:
            arr = self._disk_load(table, name)
            if arr is None:
                gen = getattr(self, f"_{table}_{name}", None)
                if gen is None:
                    raise KeyError(f"no column {table}.{name}")
                arr = gen()
                self._disk_store(table, name, arr)
            arr.setflags(write=False)  # cached arrays are shared with scans
            self._cache[key] = arr
        return self._cache[key]

    # Generated columns are deterministic functions of (sf, table,
    # column), so a host disk cache is safe and makes a fresh process
    # at SF>=1 start in seconds instead of minutes (the reference pays
    # the same cost once per JVM via its in-process tpch generator).
    def _disk_path(self, table: str, name: str) -> str | None:
        root = os.environ.get(
            "TRINO_TPU_DATA_CACHE",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__))
                ))),
                ".tpch_cache",
            ),
        )
        if root == "off" or self.sf < 0.5:
            return None
        return os.path.join(root, f"sf{self.sf:g}_{table}_{name}.npy")

    def stats_path(self, table: str) -> str | None:
        """Disk-cache path for the table's column stats (JSON)."""
        p = self._disk_path(table, "stats")
        return None if p is None else p[:-4] + ".json"

    def _disk_load(self, table: str, name: str) -> np.ndarray | None:
        path = self._disk_path(table, name)
        if path is None or not os.path.exists(path):
            return None
        # never unpickle: the cache dir is overridable/shared, and
        # pickled .npy files are an arbitrary-code-execution surface.
        # Strings are stored as fixed-width unicode (see _disk_store).
        try:
            arr = np.load(path, allow_pickle=False)
        except ValueError:  # legacy pickled file: regenerate instead
            return None
        if arr.dtype.kind == "U":
            arr = arr.astype(object)
        return arr

    def _disk_store(self, table: str, name: str, arr: np.ndarray) -> None:
        path = self._disk_path(table, name)
        if path is None:
            return
        os.makedirs(os.path.dirname(path), exist_ok=True)
        if arr.dtype == object:  # varchar columns: pickle-free layout
            arr = arr.astype("U")
        # np.save appends .npy unless the name already ends with it
        tmp = f"{path[:-4]}.tmp{os.getpid()}.npy"
        np.save(tmp, arr, allow_pickle=False)
        os.replace(tmp, path)

    def table(self, table: str) -> dict[str, np.ndarray]:
        return {c: self.column(table, c) for c in SCHEMAS[table].column_names}

    # ---- helpers ---------------------------------------------------------
    def _words(self, rng, n, k, vocab=text.COMMENT_WORDS) -> np.ndarray:
        """n comments of k words each, vectorized."""
        vocab_arr = np.asarray(vocab)
        idx = rng.integers(0, len(vocab_arr), size=(n, k))
        parts = vocab_arr[idx]
        out = parts[:, 0]
        for j in range(1, k):
            out = np.char.add(np.char.add(out, " "), parts[:, j])
        return out.astype(object)

    @staticmethod
    def _numbered(prefix: str, keys: np.ndarray, width: int = 9) -> np.ndarray:
        return np.array([f"{prefix}#{k:0{width}d}" for k in keys], dtype=object)

    @staticmethod
    def _phone(nationkeys: np.ndarray, keys: np.ndarray) -> np.ndarray:
        cc = nationkeys + 10
        a = (keys * 31 + 7) % 900 + 100
        b = (keys * 17 + 3) % 900 + 100
        c = (keys * 13 + 11) % 9000 + 1000
        return np.array(
            [f"{w}-{x}-{y}-{z}" for w, x, y, z in zip(cc, a, b, c)], dtype=object
        )

    def _acctbal(self, table: str) -> np.ndarray:
        n = {"supplier": self.n_supplier, "customer": self.n_customer}[table]
        rng = self._rng(table, "acctbal")
        return rng.integers(-99_999, 999_999, size=n, dtype=np.int64)  # cents

    # ---- region / nation -------------------------------------------------
    def _region_regionkey(self):
        return np.arange(5, dtype=np.int64)

    def _region_name(self):
        return np.asarray(text.REGIONS, dtype=object)

    def _region_comment(self):
        return self._words(self._rng("region", "comment"), 5, 6)

    def _nation_nationkey(self):
        return np.arange(25, dtype=np.int64)

    def _nation_name(self):
        return np.asarray([n for n, _ in text.NATIONS], dtype=object)

    def _nation_regionkey(self):
        return np.asarray([r for _, r in text.NATIONS], dtype=np.int64)

    def _nation_comment(self):
        return self._words(self._rng("nation", "comment"), 25, 8)

    # ---- supplier --------------------------------------------------------
    def _supplier_suppkey(self):
        return np.arange(1, self.n_supplier + 1, dtype=np.int64)

    def _supplier_name(self):
        return self._numbered("Supplier", self.column("supplier", "suppkey"))

    def _supplier_address(self):
        return self._words(self._rng("supplier", "address"), self.n_supplier, 3)

    def _supplier_nationkey(self):
        return self._rng("supplier", "nation").integers(
            0, 25, size=self.n_supplier, dtype=np.int64
        )

    def _supplier_phone(self):
        return self._phone(
            self.column("supplier", "nationkey"), self.column("supplier", "suppkey")
        )

    def _supplier_acctbal(self):
        return self._acctbal("supplier")

    def _supplier_comment(self):
        # spec: ~5 in 10k get "Customer ... Complaints" (q16 anti-filter)
        out = self._words(self._rng("supplier", "comment"), self.n_supplier, 9)
        rng = self._rng("supplier", "complaints")
        hits = rng.integers(0, self.n_supplier, size=max(1, self.n_supplier // 2000))
        for i in hits:
            out[i] = out[i] + " Customer extra care Complaints"
        return out

    # ---- customer --------------------------------------------------------
    def _customer_custkey(self):
        return np.arange(1, self.n_customer + 1, dtype=np.int64)

    def _customer_name(self):
        return self._numbered("Customer", self.column("customer", "custkey"))

    def _customer_address(self):
        return self._words(self._rng("customer", "address"), self.n_customer, 3)

    def _customer_nationkey(self):
        return self._rng("customer", "nation").integers(
            0, 25, size=self.n_customer, dtype=np.int64
        )

    def _customer_phone(self):
        return self._phone(
            self.column("customer", "nationkey"), self.column("customer", "custkey")
        )

    def _customer_acctbal(self):
        return self._acctbal("customer")

    def _customer_mktsegment(self):
        idx = self._rng("customer", "segment").integers(
            0, len(text.SEGMENTS), size=self.n_customer
        )
        return np.asarray(text.SEGMENTS, dtype=object)[idx]

    def _customer_comment(self):
        return self._words(self._rng("customer", "comment"), self.n_customer, 8)

    # ---- part ------------------------------------------------------------
    def _part_partkey(self):
        return np.arange(1, self.n_part + 1, dtype=np.int64)

    def _part_name(self):
        rng = self._rng("part", "name")
        return self._words(rng, self.n_part, 5, vocab=text.PART_NAME_WORDS)

    def _part_mfgr(self):
        m = self._mfgr_num()
        return np.array([f"Manufacturer#{v}" for v in m], dtype=object)

    def _mfgr_num(self):
        return self._rng("part", "mfgr").integers(1, 6, size=self.n_part)

    def _part_brand(self):
        m = self._mfgr_num()
        n = self._rng("part", "brand").integers(1, 6, size=self.n_part)
        return np.array([f"Brand#{a}{b}" for a, b in zip(m, n)], dtype=object)

    def _part_type(self):
        rng = self._rng("part", "type")
        i1 = rng.integers(0, len(text.TYPE_SYLLABLE_1), size=self.n_part)
        i2 = rng.integers(0, len(text.TYPE_SYLLABLE_2), size=self.n_part)
        i3 = rng.integers(0, len(text.TYPE_SYLLABLE_3), size=self.n_part)
        s1 = np.asarray(text.TYPE_SYLLABLE_1, dtype=object)[i1]
        s2 = np.asarray(text.TYPE_SYLLABLE_2, dtype=object)[i2]
        s3 = np.asarray(text.TYPE_SYLLABLE_3, dtype=object)[i3]
        return s1 + " " + s2 + " " + s3

    def _part_size(self):
        return self._rng("part", "size").integers(
            1, 51, size=self.n_part, dtype=np.int32
        )

    def _part_container(self):
        rng = self._rng("part", "container")
        i1 = rng.integers(0, len(text.CONTAINER_SYLLABLE_1), size=self.n_part)
        i2 = rng.integers(0, len(text.CONTAINER_SYLLABLE_2), size=self.n_part)
        s1 = np.asarray(text.CONTAINER_SYLLABLE_1, dtype=object)[i1]
        s2 = np.asarray(text.CONTAINER_SYLLABLE_2, dtype=object)[i2]
        return s1 + " " + s2

    @staticmethod
    def _retail_cents(pk: np.ndarray) -> np.ndarray:
        # spec formula, in cents: 90000 + ((pk/10) mod 20001) + 100*(pk mod 1000)
        return 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1_000)

    def _part_retailprice(self):
        return self._retail_cents(self.column("part", "partkey"))

    def _part_comment(self):
        return self._words(self._rng("part", "comment"), self.n_part, 4)

    # ---- partsupp --------------------------------------------------------
    def _partsupp_partkey(self):
        return np.repeat(self.column("part", "partkey"), 4)

    def _partsupp_suppkey(self):
        # spec: supplier j of part pk is
        # (pk + j*(S/4 + (pk-1)/S)) mod S + 1   — spreads the 4 suppliers
        pk = self.column("partsupp", "partkey")
        j = np.tile(np.arange(4, dtype=np.int64), self.n_part)
        s = self.n_supplier
        return (pk + j * (s // 4 + (pk - 1) // s)) % s + 1

    def _partsupp_availqty(self):
        return self._rng("partsupp", "availqty").integers(
            1, 10_000, size=self.n_partsupp, dtype=np.int32
        )

    def _partsupp_supplycost(self):
        return self._rng("partsupp", "supplycost").integers(
            100, 100_001, size=self.n_partsupp, dtype=np.int64
        )

    def _partsupp_comment(self):
        return self._words(self._rng("partsupp", "comment"), self.n_partsupp, 10)

    # ---- orders ----------------------------------------------------------
    def _orders_orderkey(self):
        # spec: order keys are sparse — 8 used out of every 32
        i = np.arange(self.n_orders, dtype=np.int64)
        return (i // 8) * 32 + (i % 8) + 1

    def _orders_custkey(self):
        # spec: only customers with custkey % 3 != 0 place orders
        rng = self._rng("orders", "custkey")
        raw = rng.integers(1, self.n_customer + 1, size=self.n_orders, dtype=np.int64)
        raw[raw % 3 == 0] += 1
        raw[raw > self.n_customer] = 1
        return raw

    def _orders_orderdate(self):
        rng = self._rng("orders", "orderdate")
        return rng.integers(
            MIN_ORDER_DATE, MAX_ORDER_DATE + 1, size=self.n_orders, dtype=np.int32
        )

    def _orders_orderpriority(self):
        idx = self._rng("orders", "priority").integers(
            0, len(text.PRIORITIES), size=self.n_orders
        )
        return np.asarray(text.PRIORITIES, dtype=object)[idx]

    def _orders_clerk(self):
        n_clerks = max(1, int(1000 * self.sf))
        c = self._rng("orders", "clerk").integers(1, n_clerks + 1, size=self.n_orders)
        return self._numbered("Clerk", c)

    def _orders_shippriority(self):
        return np.zeros(self.n_orders, dtype=np.int32)

    def _orders_comment(self):
        return self._words(self._rng("orders", "comment"), self.n_orders, 6)

    def _orders_totalprice(self):
        ok = self.column("lineitem", "orderkey")
        ext = self.column("lineitem", "extendedprice")
        disc = self.column("lineitem", "discount")
        tax = self.column("lineitem", "tax")
        line = ext * (100 - disc) * (100 + tax) // 10_000
        # lineitem rows are grouped by order in generation order
        counts = self._line_counts()
        ends = np.cumsum(line)
        idx = np.cumsum(counts) - 1
        totals = ends[idx]
        totals[1:] -= ends[idx[:-1]]
        return totals

    def _orders_orderstatus(self):
        status = self.column("lineitem", "linestatus")
        counts = self._line_counts()
        is_f = (status == "F").astype(np.int64)
        ends = np.cumsum(is_f)
        idx = np.cumsum(counts) - 1
        f_per_order = ends[idx].copy()
        f_per_order[1:] -= ends[idx[:-1]]
        out = np.full(self.n_orders, "P", dtype=object)
        out[f_per_order == counts] = "F"
        out[f_per_order == 0] = "O"
        return out

    # ---- lineitem --------------------------------------------------------
    def _line_counts(self) -> np.ndarray:
        key = ("lineitem", "__counts__")
        if key not in self._cache:
            rng = self._rng("lineitem", "counts")
            self._cache[key] = rng.integers(
                1, 8, size=self.n_orders, dtype=np.int64
            )
        return self._cache[key]

    @property
    def n_lineitem(self) -> int:
        return int(self._line_counts().sum())

    def _lineitem_orderkey(self):
        return np.repeat(self.column("orders", "orderkey"), self._line_counts())

    def _lineitem_linenumber(self):
        counts = self._line_counts()
        total = counts.sum()
        starts = np.repeat(np.cumsum(counts) - counts, counts)
        return (np.arange(total) - starts + 1).astype(np.int32)

    def _lineitem_partkey(self):
        rng = self._rng("lineitem", "partkey")
        return rng.integers(1, self.n_part + 1, size=self.n_lineitem, dtype=np.int64)

    def _lineitem_suppkey(self):
        pk = self.column("lineitem", "partkey")
        j = self._rng("lineitem", "suppsel").integers(
            0, 4, size=self.n_lineitem, dtype=np.int64
        )
        s = self.n_supplier
        return (pk + j * (s // 4 + (pk - 1) // s)) % s + 1

    def _lineitem_quantity(self):
        q = self._rng("lineitem", "quantity").integers(
            1, 51, size=self.n_lineitem, dtype=np.int64
        )
        return q * 100  # decimal(15,2) cents

    def _lineitem_extendedprice(self):
        # spec: extendedprice = quantity * part.retailprice
        retail = self._retail_cents(self.column("lineitem", "partkey"))
        qty = self.column("lineitem", "quantity") // 100
        return qty * retail

    def _lineitem_discount(self):
        return self._rng("lineitem", "discount").integers(
            0, 11, size=self.n_lineitem, dtype=np.int64
        )

    def _lineitem_tax(self):
        return self._rng("lineitem", "tax").integers(
            0, 9, size=self.n_lineitem, dtype=np.int64
        )

    def _lineitem_shipdate(self):
        od = np.repeat(self.column("orders", "orderdate"), self._line_counts())
        d = self._rng("lineitem", "shipdate").integers(
            1, 122, size=self.n_lineitem, dtype=np.int32
        )
        return (od + d).astype(np.int32)

    def _lineitem_commitdate(self):
        od = np.repeat(self.column("orders", "orderdate"), self._line_counts())
        d = self._rng("lineitem", "commitdate").integers(
            30, 91, size=self.n_lineitem, dtype=np.int32
        )
        return (od + d).astype(np.int32)

    def _lineitem_receiptdate(self):
        sd = self.column("lineitem", "shipdate")
        d = self._rng("lineitem", "receiptdate").integers(
            1, 31, size=self.n_lineitem, dtype=np.int32
        )
        return (sd + d).astype(np.int32)

    def _lineitem_returnflag(self):
        rd = self.column("lineitem", "receiptdate")
        coin = self._rng("lineitem", "returnflag").integers(0, 2, size=self.n_lineitem)
        out = np.full(self.n_lineitem, "N", dtype=object)
        returned = rd <= CURRENT_DATE
        out[returned & (coin == 0)] = "R"
        out[returned & (coin == 1)] = "A"
        return out

    def _lineitem_linestatus(self):
        sd = self.column("lineitem", "shipdate")
        out = np.full(self.n_lineitem, "O", dtype=object)
        out[sd <= CURRENT_DATE] = "F"
        return out

    def _lineitem_shipinstruct(self):
        idx = self._rng("lineitem", "shipinstruct").integers(
            0, len(text.SHIP_INSTRUCTIONS), size=self.n_lineitem
        )
        return np.asarray(text.SHIP_INSTRUCTIONS, dtype=object)[idx]

    def _lineitem_shipmode(self):
        idx = self._rng("lineitem", "shipmode").integers(
            0, len(text.SHIP_MODES), size=self.n_lineitem
        )
        return np.asarray(text.SHIP_MODES, dtype=object)[idx]

    def _lineitem_comment(self):
        return self._words(self._rng("lineitem", "comment"), self.n_lineitem, 4)
