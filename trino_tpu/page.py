"""Device-resident columnar pages.

The analog of the reference's columnar batch (SPI/Page.java:31 holding a
sealed Block hierarchy, SPI/block/Block.java:26). A ``Page`` here is a
struct-of-arrays over *device* memory:

- every column is one fixed-width JAX array (``Column.data``)
- nulls are a separate boolean validity array per column — exactly the
  reference's separate null masks in ValueBlocks, which map 1:1 onto
  TPU masks
- a page-level ``mask`` marks live rows: filters do not compact (that
  would be a dynamic shape); they clear mask bits. Compaction happens
  only at host materialization or before expensive downstream ops
  (the analog of Page.compact, SPI/Page.java:180)
- VARCHAR columns carry a host-side sorted ``StringDictionary``; device
  data holds int32 codes (replacing VariableWidthBlock's pointer
  chasing with a dictionary-encode-early strategy)

Capacities are padded to power-of-two buckets so XLA compiles one
program per pipeline, not per batch size.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from trino_tpu import telemetry, types as T

__all__ = [
    "StringDictionary", "HashStringPool", "HashCollision", "ArrayPool",
    "MapPool", "RowPool",
    "Column", "Page", "pad_capacity", "content_hash64",
]


def content_hash64(strings: np.ndarray) -> np.ndarray:
    """Deterministic 64-bit content hash of a string array, vectorized.

    Views the fixed-width UCS4 representation as uint32 lanes and folds
    them with an FNV-style polynomial — one vector op per character
    column instead of a per-row Python loop. Unlike ``hash()`` (which
    PYTHONHASHSEED randomizes per process) the result is identical in
    every process, so fleet workers hashing the same value — for spool
    partitioning or HLL registers — always agree."""
    arr = np.asarray(strings)
    if arr.dtype.kind != "U":
        arr = arr.astype(str)
    n = len(arr)
    if n == 0:
        return np.zeros(0, dtype=np.uint64)
    width = max(arr.dtype.itemsize // 4, 1)
    lanes = np.ascontiguousarray(arr).view(np.uint32).reshape(n, width)
    h = np.full(n, 0xCBF29CE484222325, dtype=np.uint64)
    prime = np.uint64(0x100000001B3)
    with np.errstate(over="ignore"):
        for j in range(width):
            lane = lanes[:, j].astype(np.uint64)
            # skip zero lanes (UCS4 tail padding): the hash must not
            # depend on the array's fixed width, or the same string in
            # two differently-sized columns lands in different spool
            # partitions
            upd = (h ^ lane) * prime
            h = np.where(lane != 0, upd, h)
        # final avalanche so short strings spread over all 64 bits
        h ^= h >> np.uint64(33)
        h *= np.uint64(0xFF51AFD7ED558CCD)
        h ^= h >> np.uint64(33)
    return h


def pad_capacity(n: int, minimum: int = 8) -> int:
    """Round up to a power of two or 1.5x a power of two (>= 96).

    The bucket family bounds the number of XLA programs while keeping
    worst-case padding waste at 33% instead of 100%; every bucket stays
    divisible by 8 so mesh sharding divides evenly."""
    c = max(int(n), minimum)
    p = 1 << (c - 1).bit_length()
    mid = (p // 4) * 3
    if mid >= max(c, 96):
        return mid
    return p


class StringDictionary:
    """Sorted, de-duplicated host-side string pool.

    Code order == lexicographic order, so <, >, ORDER BY and MIN/MAX on
    VARCHAR run entirely on device codes.
    """

    __slots__ = ("values", "_index", "_fp")

    def __init__(self, values: np.ndarray):
        # values must be sorted & unique (callers use from_strings)
        self.values = values
        self._index: dict[str, int] | None = None
        self._fp: bytes | None = None

    @property
    def fingerprint(self) -> bytes:
        """Content digest — the cache identity for compiled programs.
        Programs bake dictionary-dependent constants (encoded literal
        codes), so equal CONTENTS means an equal program; object
        identity (``id``) is too strict and makes every spool-rebuilt
        dictionary a fresh jit key (unbounded retrace + retained
        jaxprs under multi-statement serving)."""
        fp = self._fp
        if fp is None:
            import hashlib

            arr = np.asarray(self.values, dtype=str)
            h = hashlib.blake2b(digest_size=16)
            h.update(str(arr.dtype).encode())
            h.update(arr.tobytes())
            fp = self._fp = h.digest()
        return fp

    @staticmethod
    def from_strings(strings: Sequence[str]) -> tuple["StringDictionary", np.ndarray]:
        """Build a dictionary and return (dict, int32 codes)."""
        arr = np.asarray(strings, dtype=object)
        uniq, codes = np.unique(arr.astype(str), return_inverse=True)
        return StringDictionary(uniq), codes.astype(np.int32)

    def __len__(self) -> int:
        return len(self.values)

    def encode_one(self, s: str) -> int:
        """Code for s, or -1 if absent."""
        i = int(np.searchsorted(self.values, s))
        if i < len(self.values) and self.values[i] == s:
            return i
        return -1

    def decode(self, codes: np.ndarray) -> np.ndarray:
        out = self.values[np.clip(codes, 0, len(self.values) - 1)]
        return out

    def union(self, other: "StringDictionary"):
        """Merge two dictionaries.

        Returns (merged, remap_self, remap_other) where remap_x is an
        int32 host array mapping old codes -> merged codes (applied on
        device with a gather).
        """
        merged = np.union1d(self.values, other.values)
        remap_a = np.searchsorted(merged, self.values).astype(np.int32)
        remap_b = np.searchsorted(merged, other.values).astype(np.int32)
        return StringDictionary(merged), remap_a, remap_b


_POOL_TOKENS = iter(range(1, 1 << 62))


class HashStringPool:
    """High-cardinality VARCHAR representation (SURVEY §7 hard-parts):
    the device column carries [hash64, source_row_id] lanes; this pool
    holds the HOST strings the id lane indexes, plus a one-time
    injectivity proof.

    Unlike sorted-dictionary codes, hash codes are GLOBALLY consistent
    (hash(s) is the same in every column), so joins/exchanges never
    remap — only the cross-pool injectivity check must pass. Building
    one costs a single hash pass (~0.6 s for 6M strings vs ~15 s for
    the sorted np.unique dictionary at 4.8M NDV).

    ``token`` is a process-unique id for cache keys (``id()`` can
    alias a freed pool's address; tokens never repeat).
    """

    __slots__ = (
        "values", "token", "_hashes", "_sorted", "_joinable",
    )

    def __init__(self, values: np.ndarray):
        self.values = values  # host object array, id lane indexes it
        self.token = next(_POOL_TOKENS)
        self._hashes: np.ndarray | None = None
        #: (unique sorted hashes, one representative string per hash)
        self._sorted: tuple[np.ndarray, np.ndarray] | None = None
        self._joinable: set[int] = set()

    def hashes(self) -> np.ndarray:
        if self._hashes is None:
            self._hashes = np.fromiter(
                (hash(s) for s in self.values),
                dtype=np.int64, count=len(self.values),
            )
        return self._hashes

    def verify_injective(self) -> None:
        """Prove hash64 is injective on this pool's values (memoized,
        vectorized: sort hashes, string-compare only within equal-hash
        runs — any run holding two distinct strings has an adjacent
        differing pair). A collision (probability ~n^2/2^64) raises —
        callers rebuild with a sorted dictionary."""
        if self._sorted is not None:
            return
        h = self.hashes()
        if len(h) == 0:
            self._sorted = (h, self.values)
            return
        order = np.argsort(h, kind="stable")
        hs = h[order]
        vs = self.values[order]
        same_h = hs[1:] == hs[:-1]
        if same_h.any():
            diff = same_h & (vs[1:] != vs[:-1])
            if diff.any():
                i = int(np.argmax(diff))
                raise HashCollision(vs[i], vs[i + 1])
        # dedupe to one representative per hash (injectivity proven)
        first = np.concatenate([[True], ~same_h])
        self._sorted = (hs[first], vs[first])

    def verify_joinable(self, other: "HashStringPool") -> None:
        """Prove injectivity across BOTH pools (join exactness);
        memoized per pool pair and fully vectorized: compare the
        representative strings at hash values common to both sides."""
        if other.token in self._joinable or other is self:
            return
        self.verify_injective()
        other.verify_injective()
        ha, va = self._sorted
        hb, vb = other._sorted
        common, ia, ib = np.intersect1d(
            ha, hb, assume_unique=True, return_indices=True
        )
        if len(common) and (va[ia] != vb[ib]).any():
            bad = int(np.argmax(va[ia] != vb[ib]))
            raise HashCollision(va[ia][bad], vb[ib][bad])
        self._joinable.add(other.token)
        other._joinable.add(self.token)


class ArrayPool:
    """Host-side offsets+values columnar store for ARRAY columns (the
    ArrayBlock analog, SPI/block/ArrayBlock.java): ``offsets`` is
    int64[n+1], ``values`` the flat element buffer in STORAGE form
    (objects for varchar elements). Device columns carry int32 handles
    into this pool; descriptor gathers on device never disturb the
    flat buffer, exactly like dictionary codes vs the string pool."""

    __slots__ = ("offsets", "values", "element", "token")

    def __init__(self, offsets: np.ndarray, values: np.ndarray, element):
        self.offsets = offsets
        self.values = values
        self.element = element
        self.token = next(_POOL_TOKENS)

    def __len__(self) -> int:
        return len(self.offsets) - 1

    @staticmethod
    def from_pylists(lists, element) -> tuple["ArrayPool", np.ndarray]:
        """Build a pool from python sequences; returns (pool, handles).
        None entries produce handle 0 with the caller masking validity."""
        offsets = np.zeros(len(lists) + 1, dtype=np.int64)
        flat = []
        for i, v in enumerate(lists):
            if v is None:
                offsets[i + 1] = offsets[i]
                continue
            flat.extend(v)
            offsets[i + 1] = offsets[i] + len(v)
        from trino_tpu import types as T

        if isinstance(element, T.VarcharType):
            values = np.asarray(flat, dtype=object)
        else:
            values = np.asarray(
                flat if flat else [], dtype=element.np_dtype
            )
        return (
            ArrayPool(offsets, values, element),
            np.arange(len(lists), dtype=np.int32),
        )

    def lengths(self) -> np.ndarray:
        return np.diff(self.offsets).astype(np.int64)

    def get(self, handle: int) -> list:
        lo, hi = self.offsets[handle], self.offsets[handle + 1]
        return list(self.values[lo:hi])

    def decode(self, handles: np.ndarray) -> np.ndarray:
        """Handles -> object array of python lists (the one shared
        decode for result fetch / host spill / page_to_host)."""
        out = np.empty(len(handles), dtype=object)
        for i, h in enumerate(handles):
            out[i] = self.get(int(h))
        return out


def _storage_buffer(flat: list, element) -> np.ndarray:
    if isinstance(
        element, (T.VarcharType, T.MapType, T.RowType, T.ArrayType)
    ) or any(v is None for v in flat):
        # NULL entries keep the buffer in object form so decode
        # round-trips None (fixed-width functions over such a pool
        # reject at compile time)
        return np.asarray(flat, dtype=object)
    return np.asarray(flat if flat else [], dtype=element.np_dtype)


class MapPool:
    """Host-side store for MAP columns (SPI/type/MapType.java:58 /
    SPI/block/MapBlock.java analog): one offsets array, two parallel
    flat buffers (keys, values) in STORAGE form. Device columns carry
    int32 handles; map functions compile host LUTs over the pool and
    gather by handle — the ArrayPool design with a second buffer."""

    __slots__ = ("offsets", "keys", "values", "key_type", "value_type", "token")

    def __init__(self, offsets, keys, values, key_type, value_type):
        self.offsets = offsets
        self.keys = keys
        self.values = values
        self.key_type = key_type
        self.value_type = value_type
        self.token = next(_POOL_TOKENS)

    def __len__(self) -> int:
        return len(self.offsets) - 1

    @staticmethod
    def from_pymaps(maps, key_type, value_type) -> tuple["MapPool", np.ndarray]:
        """Build from python dicts / (k, v)-pair sequences; returns
        (pool, handles). None entries produce an empty map with the
        caller masking validity."""
        offsets = np.zeros(len(maps) + 1, dtype=np.int64)
        ks, vs = [], []
        for i, m in enumerate(maps):
            if m is None:
                offsets[i + 1] = offsets[i]
                continue
            pairs = list(m.items()) if isinstance(m, dict) else list(m)
            # keep-FIRST dedup so get() and the subscript LUT (which
            # takes the first match) agree; the map() constructor
            # rejects explicit duplicates before reaching here
            seen = set()
            n_kept = 0
            for k, v in pairs:
                if k in seen:
                    continue
                seen.add(k)
                ks.append(k)
                vs.append(v)
                n_kept += 1
            offsets[i + 1] = offsets[i] + n_kept
        return (
            MapPool(
                offsets,
                _storage_buffer(ks, key_type),
                _storage_buffer(vs, value_type),
                key_type,
                value_type,
            ),
            np.arange(len(maps), dtype=np.int32),
        )

    def lengths(self) -> np.ndarray:
        return np.diff(self.offsets).astype(np.int64)

    def get(self, handle: int) -> dict:
        lo, hi = self.offsets[handle], self.offsets[handle + 1]
        return dict(zip(self.keys[lo:hi], self.values[lo:hi]))

    def decode(self, handles: np.ndarray) -> np.ndarray:
        out = np.empty(len(handles), dtype=object)
        for i, h in enumerate(handles):
            out[i] = self.get(int(h))
        return out


class RowPool:
    """Host-side store for ROW columns (SPI/type/RowType.java:67 /
    SPI/block/RowBlock.java analog): one storage-form column (+ null
    mask) per field; device columns carry int32 handles. Field access
    is a host LUT over the field column + one device gather."""

    __slots__ = ("fields", "type", "token")

    def __init__(self, fields, type_):
        #: list[(np.ndarray values, np.ndarray | None valid)] per field
        self.fields = fields
        self.type = type_
        self.token = next(_POOL_TOKENS)

    def __len__(self) -> int:
        return len(self.fields[0][0]) if self.fields else 0

    @staticmethod
    def from_pytuples(tuples, type_) -> tuple["RowPool", np.ndarray]:
        n = len(tuples)
        fields = []
        for fi, (_fn, ft) in enumerate(type_.fields):
            raw = [
                None if t is None else t[fi] for t in tuples
            ]
            valid = np.asarray([v is not None for v in raw], dtype=np.bool_)
            # storage: nulls become the type's zero value; the mask
            # carries the truth
            filled = [
                ("" if isinstance(ft, T.VarcharType) else 0)
                if v is None else v
                for v in raw
            ]
            vals = _storage_buffer(filled, ft)
            fields.append((vals, None if valid.all() else valid))
        return (
            RowPool(fields, type_),
            np.arange(n, dtype=np.int32),
        )

    def get(self, handle: int):
        out = []
        for vals, valid in self.fields:
            if valid is not None and not valid[handle]:
                out.append(None)
            else:
                v = vals[handle]
                out.append(v.item() if isinstance(v, np.generic) else v)
        return tuple(out)

    def decode(self, handles: np.ndarray) -> np.ndarray:
        out = np.empty(len(handles), dtype=object)
        for i, h in enumerate(handles):
            out[i] = self.get(int(h))
        return out


class HashCollision(RuntimeError):
    """Two distinct strings share a hash64 — astronomically rare; the
    caller rebuilds the column with a sorted dictionary."""

    def __init__(self, a, b):
        super().__init__(f"hash collision: {a!r} vs {b!r}")


@dataclass
class Column:
    """One device column: fixed-width data + optional validity + dict.

    VARCHAR columns take one of two encodings: sorted-dictionary codes
    (``dictionary`` set — supports ordering/range ops) or hash codes
    (``hash_pool`` set, data is [cap, 2] = (hash64, source_row_id) —
    equality-only, for high-NDV columns where a sorted dictionary build
    is the startup cliff)."""

    type: T.DataType
    data: jnp.ndarray
    valid: jnp.ndarray | None = None  # None => all valid
    dictionary: StringDictionary | None = None
    hash_pool: HashStringPool | None = None
    #: ARRAY/MAP/ROW columns: host variable-width store indexed by the
    #: int32 handle lanes in ``data`` (ArrayPool | MapPool | RowPool —
    #: all expose get()/decode() so downstream code treats them
    #: uniformly)
    array_pool: "ArrayPool | MapPool | RowPool | None" = None

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def with_dictionary(self, d: StringDictionary) -> "Column":
        return replace(self, dictionary=d)

    @staticmethod
    def from_numpy(
        type_: T.DataType,
        values: np.ndarray,
        valid: np.ndarray | None = None,
        capacity: int | None = None,
        dictionary: StringDictionary | None = None,
    ) -> "Column":
        n = len(values)
        cap = capacity or pad_capacity(n)
        if isinstance(type_, (T.ArrayType, T.MapType, T.RowType)):
            if isinstance(type_, T.ArrayType):
                pool, handles = ArrayPool.from_pylists(values, type_.element)
            elif isinstance(type_, T.MapType):
                pool, handles = MapPool.from_pymaps(
                    values, type_.key, type_.value
                )
            else:
                pool, handles = RowPool.from_pytuples(values, type_)
            data = np.zeros(cap, dtype=np.int32)
            data[:n] = handles
            col_valid = None
            nulls = np.asarray(
                [v is None for v in values], dtype=np.bool_
            )
            if valid is not None or nulls.any():
                v = np.zeros(cap, dtype=np.bool_)
                v[:n] = (
                    np.ones(n, dtype=np.bool_) if valid is None
                    else np.asarray(valid, dtype=np.bool_)
                ) & ~nulls
                col_valid = jnp.asarray(v)
            return Column(
                type_, jnp.asarray(data), col_valid, array_pool=pool
            )
        if type_.is_dictionary and dictionary is None:
            dictionary, values = StringDictionary.from_strings(values)
        arr = np.asarray(values)
        if arr.dtype != object and arr.ndim == 2:
            # two-limb decimal columns are [n, 2]
            data = np.zeros((cap, arr.shape[1]), dtype=type_.np_dtype)
        else:
            data = np.zeros(cap, dtype=type_.np_dtype)
        data[:n] = np.asarray(values, dtype=type_.np_dtype)
        col_valid = None
        if valid is not None:
            v = np.zeros(cap, dtype=np.bool_)
            v[:n] = valid
            col_valid = jnp.asarray(v)
        return Column(type_, jnp.asarray(data), col_valid, dictionary)

    def to_numpy(self, sel: np.ndarray | None = None):
        """Materialize to host values (Python-friendly), None for nulls."""
        data = np.asarray(self.data)
        valid = None if self.valid is None else np.asarray(self.valid)
        if sel is not None:
            data = data[sel]
            valid = None if valid is None else valid[sel]
        if self.dictionary is not None:
            out = self.dictionary.decode(data).astype(object)
        elif self.hash_pool is not None:
            out = self.hash_pool.values[data[:, 1]].astype(object)
        elif self.array_pool is not None:
            out = self.array_pool.decode(data)
        elif isinstance(self.type, T.DecimalType):
            out = data  # unscaled; rendering applies the scale
        else:
            out = data
        return out, valid


@dataclass
class Page:
    """A batch of rows: named device columns + a live-row mask.

    ``names`` mirror planner symbols; operators address columns by
    position like the reference's channels
    (MAIN/sql/planner/LocalExecutionPlanner.java layout maps).
    """

    names: list[str]
    columns: list[Column]
    mask: jnp.ndarray  # bool[capacity]; True = live row
    #: host-known live-row count (avoids a device sync when set)
    known_rows: int | None = None
    #: True when live rows occupy positions [0, known_rows) exactly
    packed: bool = False
    #: the symbol whose packed key words the live rows ascend on, as the
    #: connector declared (``Connector.sorted_by``) — None: no order
    #: known. Opt-in and lost by default: only the whole-table resident
    #: scan (``LocalExecutor._TableScan``) sets it, and whatever builds
    #: another page from this one (a join, a sort, an exchange, a union,
    #: a compaction, a slice) builds it without. A run cut at a page
    #: border would be two partial groups, so the pages that hold a
    #: row range only — a split (``_scan_split``), a domain-pruned scan,
    #: a chunk of ``_run_chain_chunked``, a batch of a streamed scan
    #: (``exec/stream_scan.py``, ``exec/spill.py``), a mesh shard
    #: (``ShardedPage`` has no such field) — never carry it. A grouped
    #: Aggregate over exactly this key, reached through Projects only,
    #: then groups by the runs in place (``kernels.run_group``), which
    #: checks the order on the device.
    ordered_on: str | None = None

    def __post_init__(self):
        assert len(self.names) == len(self.columns)

    @property
    def capacity(self) -> int:
        return self.mask.shape[0]

    def column(self, name: str) -> Column:
        return self.columns[self.names.index(name)]

    def num_rows(self) -> int:
        """Live row count (device sync unless already host-known)."""
        if self.known_rows is not None:
            return self.known_rows
        with telemetry.child_span("host_sync", site="num_rows"):
            return int(jnp.sum(self.mask))

    @staticmethod
    def from_arrays(
        named: dict[str, tuple[T.DataType, np.ndarray]],
        capacity: int | None = None,
    ) -> "Page":
        lengths = {name: len(v[1]) for name, v in named.items()}
        if len(set(lengths.values())) > 1:
            raise ValueError(f"ragged columns: {lengths}")
        n = next(iter(lengths.values()))
        cap = capacity or pad_capacity(n)
        names, cols = [], []
        for name, (type_, values) in named.items():
            names.append(name)
            cols.append(Column.from_numpy(type_, values, capacity=cap))
        mask = np.zeros(cap, dtype=np.bool_)
        mask[:n] = True
        return Page(names, cols, jnp.asarray(mask))

    def block_until_ready(self, extra=None) -> None:
        """Wait, under a ``host_sync`` span, for the programs that fill
        this page (and ``extra``): ``to_pylist`` after it is the
        transfer and the Python rows, not the wait for a busy device."""
        import jax

        with telemetry.child_span("host_sync", site="result"):
            jax.block_until_ready((
                self.mask, [(c.data, c.valid) for c in self.columns],
                extra,
            ))

    def to_pylist(self, extra=None) -> list[tuple]:
        """Materialize live rows on host as python tuples (result fetch).

        One batched device->host transfer for the whole page (the
        serialized-results fetch of the client protocol; batching
        matters when the device link has per-call latency). Packed
        pages with a host-known row count transfer only the live
        prefix — the capacity padding never crosses the link.

        ``extra``: optional device pytree fetched IN THE SAME transfer
        (deferred overflow flags ride along with the result data);
        when given, returns (rows, extra_host)."""
        import jax

        k = self.known_rows if self.packed else None
        if k is not None:
            device_arrays = []
            for c in self.columns:
                device_arrays.append(c.data[:k])
                if c.valid is not None:
                    device_arrays.append(c.valid[:k])
            host, extra_host = jax.device_get((device_arrays, extra))
            sel = np.arange(k)
            i = 0
        else:
            device_arrays = [self.mask]
            for c in self.columns:
                device_arrays.append(c.data)
                if c.valid is not None:
                    device_arrays.append(c.valid)
            host, extra_host = jax.device_get((device_arrays, extra))
            mask = host[0]
            sel = np.nonzero(mask)[0]
            i = 1
        cols = []
        for c in self.columns:
            data = host[i]
            i += 1
            valid = None
            if c.valid is not None:
                valid = host[i][sel]
                i += 1
            data = data[sel]
            if c.dictionary is not None:
                data = c.dictionary.decode(data).astype(object)
            elif c.hash_pool is not None:
                data = c.hash_pool.values[data[:, 1]].astype(object)
            elif c.array_pool is not None:
                data = c.array_pool.decode(data)
            vals = [
                None if (valid is not None and not valid[j]) else _pyvalue(c.type, data[j])
                for j in range(len(sel))
            ]
            cols.append(vals)
        rows = [tuple(col[i] for col in cols) for i in range(len(sel))]
        if extra is not None:
            return rows, extra_host
        return rows


def _pyvalue(type_: T.DataType, v):
    if isinstance(type_, T.ArrayType):
        return [_pyvalue(type_.element, x) for x in v]
    if isinstance(type_, T.MapType):
        return {
            _pyvalue(type_.key, k): _pyvalue(type_.value, x)
            for k, x in v.items()
        }
    if isinstance(type_, T.RowType):
        return tuple(
            None if x is None else _pyvalue(ft, x)
            for (_fn, ft), x in zip(type_.fields, v)
        )
    if isinstance(type_, T.BooleanType):
        return bool(v)
    if isinstance(type_, T.DecimalType):
        # render as exact scaled decimal string -> Fraction-free float is
        # lossy; expose as python int unscaled? Tests want comparable
        # values, so render as a scaled decimal using integer math.
        import decimal

        if type_.is_long:
            # two-limb reconstruction in python ints: exact
            unscaled = int(v[0]) * (1 << 32) + int(v[1])
            return decimal.Decimal(unscaled).scaleb(-type_.scale)
        return decimal.Decimal(int(v)).scaleb(-type_.scale)
    if isinstance(type_, T.DateType):
        return T.format_date(int(v))
    if isinstance(type_, T.TimestampType):
        return T.format_timestamp(int(v))
    if isinstance(type_, (T.DoubleType, T.RealType)):
        return float(v)
    if isinstance(type_, (T.VarcharType,)):
        return str(v)
    if isinstance(type_, T.IntegerKind):
        return int(v)
    return v


def unify_dictionaries(a: Column, b: Column) -> tuple[Column, Column]:
    """Remap two VARCHAR columns onto one shared sorted dictionary.

    Host computes the merged dictionary; the code remap itself is a
    device-side gather.
    """
    if a.dictionary is None or b.dictionary is None:
        raise ValueError("both columns must be dictionary-encoded")
    if a.dictionary is b.dictionary:
        return a, b
    merged, ra, rb = a.dictionary.union(b.dictionary)
    return _remap(a, ra, merged), _remap(b, rb, merged)


def _remap(col: Column, remap: np.ndarray, merged: StringDictionary) -> Column:
    if len(remap) == 0:
        # empty dictionary: no live codes exist; keep data as-is
        return replace(col, dictionary=merged)
    return replace(
        col, data=jnp.take(jnp.asarray(remap), col.data, mode="clip"), dictionary=merged
    )
