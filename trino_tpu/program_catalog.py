"""Compiled-program catalog: every canonical-bucket XLA program as a
first-class observable object.

The reference engine can introspect its generated bytecode per driver
(OperatorStats + the compiler caches in MAIN/sql/gen/); our analog of
bytecode generation is the XLA compile, and until now a compiled chain
program was anonymous — visible only as a jit-cache hit counter. This
catalog registers one entry per canonical-bucket compile (the fused
chain programs of exec.local and exec.mesh, plus persistent-cache
deserializes routed through jit_cache), carrying:

* identity — a stable ``program_id`` (hash of the executor cache key),
  the plan-operator chain it serves, and the registering source;
* the XLA cost model — ``cost_analysis()`` FLOPs / bytes accessed;
* the **HBM footprint** — ``memory_analysis()`` argument / output /
  temp / generated-code bytes (memory governance runs on estimates;
  this is what the compiler actually reserved);
* compile seconds (first-dispatch wall at the registering site, which
  a jit-cache miss pays for trace+compile) and per-key hit counts;
* the HLO module text (truncated, content-hashed) and the
  instruction→``jax.named_scope`` map that lets a captured device
  profile attribute time to named plan operators *inside* one fused
  program (see :mod:`trino_tpu.kernel_profile`).

Cost/memory/HLO resolution is LAZY, exactly like the executor's old
``chain_cost``: the entry stores an AOT resolver
(``fn.lower(*avals).compile()``), which on first read resolves through
the persistent XLA cache as a deserialize of the program the dispatch
path already built — never a second real compile. The registry is
process-wide and bounded (``TRINO_TPU_PROGRAM_CATALOG_MAX``,
least-recently-used entries evicted past the cap); it serves
``system.runtime.programs``, ``GET /v1/programs[/{id}]`` on both
coordinator and workers, and is snapshotted into diagnostics bundles.
"""

from __future__ import annotations

import hashlib
import os
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

from trino_tpu import telemetry

__all__ = [
    "ProgramEntry", "ProgramCatalog", "CATALOG", "aot_resolver",
    "scope_map_from_hlo", "MAX_ENTRIES_ENV",
]

MAX_ENTRIES_ENV = "TRINO_TPU_PROGRAM_CATALOG_MAX"
DEFAULT_MAX_ENTRIES = 256
#: retained HLO text per entry; full modules for big chains run to MBs
HLO_TEXT_LIMIT = 120_000

#: an operator's ``jax.named_scope`` component: ``op<i>:<NodeType>`` as
#: stage.build_chain writes a chain's positions, ``op:<NodeType>`` as
#: local._named_jit writes a program that is one operator
_SCOPE_RE = re.compile(r"op\d*:[A-Za-z_]\w*")
#: the module's name at the head of its HLO text
_MODULE_RE = re.compile(r"\s*HloModule\s+([\w.\-]+)")
#: one HLO instruction definition carrying op_name metadata
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([A-Za-z_][\w.\-]*)\s*=.*?"
    r'metadata=\{[^}]*op_name="([^"]*)"',
    re.M,
)


def scope_map_from_hlo(hlo_text: str) -> dict[str, str]:
    """instruction name -> named-scope label, parsed from compiled HLO
    text. Per-instruction ``metadata={op_name="..."}`` carries the
    ``jax.named_scope`` path — including on fusion instructions, which
    is what makes attribution inside a fused program possible: the
    profiler's trace events name HLO instructions, and this map folds
    them back onto plan operators."""
    out: dict[str, str] = {}
    for instr, op_name in _INSTR_RE.findall(hlo_text or ""):
        # the innermost operator wins (a mesh chain's way into the
        # shards lies under its first position's scope)
        found = _SCOPE_RE.findall(op_name)
        if found:
            out[instr] = found[-1]
    return out


def aot_resolver(fn, abstract) -> Callable:
    """The standard lazy resolver: AOT-lower the jitted ``fn`` at the
    recorded avals and compile — a persistent-cache deserialize of the
    program the dispatch path already built."""
    def resolve():
        from trino_tpu import jit_cache

        return jit_cache.guarded(
            lambda: fn.lower(*abstract).compile(), tag="catalog-resolve"
        )

    return resolve


@dataclass
class ProgramEntry:
    """One compiled program. Identity/bookkeeping fields fill at
    registration; cost/memory/HLO fields fill on first resolve."""

    program_id: str
    source: str  # "local" | "mesh" | "persistent-cache"
    label: str  # plan-operator chain served, e.g. "Filter→Aggregate"
    key_repr: str
    created_s: float = field(default_factory=time.time)
    compile_s: float = 0.0
    hits: int = 0
    last_used_s: float = field(default_factory=time.time)
    #: lazy handle to the compiled executable (never serialized)
    resolver: Optional[Callable] = field(default=None, repr=False)
    resolved: bool = False
    resolve_error: Optional[str] = None
    # ---- cost_analysis ------------------------------------------------
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    # ---- memory_analysis (HBM footprint) ------------------------------
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    # ---- HLO ----------------------------------------------------------
    hlo_hash: Optional[str] = None
    hlo_lines: int = 0
    hlo_text: Optional[str] = field(default=None, repr=False)
    #: XLA's module name (``jit_<program>``), as a trace names it
    module: Optional[str] = None
    #: HLO instruction name -> named-scope label (profile attribution)
    scopes: dict = field(default_factory=dict, repr=False)

    def to_dict(self, include_hlo: bool = False) -> dict:
        d = {
            "program_id": self.program_id,
            "source": self.source,
            "label": self.label,
            "key": self.key_repr,
            "created_s": round(self.created_s, 3),
            "compile_s": round(self.compile_s, 4),
            "hits": self.hits,
            "resolved": self.resolved,
            "resolve_error": self.resolve_error,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "alias_bytes": self.alias_bytes,
            "hlo_hash": self.hlo_hash,
            "hlo_lines": self.hlo_lines,
            "module": self.module,
            "scope_count": len(self.scopes),
        }
        if include_hlo:
            d["hlo_text"] = self.hlo_text
            d["scopes"] = dict(self.scopes)
        return d


def _normalize_cost(analysis) -> Optional[dict]:
    """cost_analysis() across jax versions: older returns a list of
    property dicts, newer a single dict."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    if not analysis:
        return None
    return {
        "flops": float(analysis.get("flops", 0.0)),
        "bytes_accessed": float(analysis.get("bytes accessed", 0.0)),
    }


def _introspect(compiled) -> dict:
    """Pull cost/memory/HLO off a compiled executable, tolerating both
    jax's ``stages.Compiled`` and a raw ``LoadedExecutable`` (the shape
    the persistent-cache read hands back). Every probe degrades to
    None independently — a program whose backend withholds one surface
    still catalogs the others."""
    out: dict = {}
    try:
        out["cost"] = _normalize_cost(compiled.cost_analysis())
    except Exception:
        out["cost"] = None
    mem = None
    for probe in ("memory_analysis", "get_compiled_memory_stats"):
        try:
            mem = getattr(compiled, probe)()
            break
        except Exception:
            mem = None
    if mem is not None:
        out["memory"] = {
            "argument_bytes": int(
                getattr(mem, "argument_size_in_bytes", 0)
            ),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)
            ),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        }
    else:
        out["memory"] = None
    text = None
    try:
        text = compiled.as_text()
    except Exception:
        try:
            mods = compiled.hlo_modules()
            text = mods[0].to_string() if mods else None
        except Exception:
            text = None
    out["hlo_text"] = text
    return out


class ProgramCatalog:
    """Process-wide bounded registry of compiled programs."""

    def __init__(self, max_entries: int | None = None):
        if max_entries is None:
            max_entries = int(
                os.environ.get(MAX_ENTRIES_ENV, "")
                or DEFAULT_MAX_ENTRIES
            )
        self.max_entries = max(int(max_entries), 1)
        self._lock = threading.RLock()
        self._entries: dict = {}
        self.evictions = 0

    # ---- registration -------------------------------------------------

    @staticmethod
    def program_id(key) -> str:
        return hashlib.sha1(repr(key).encode()).hexdigest()[:12]

    def register(
        self, key, *, source: str, label: str,
        resolver: Optional[Callable] = None, compile_s: float = 0.0,
    ) -> ProgramEntry:
        """Register (or refresh) the entry for one executor cache key.
        Idempotent: a re-registration updates the resolver/compile
        seconds and keeps the hit history."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                entry = ProgramEntry(
                    program_id=self.program_id(key),
                    source=source,
                    label=label,
                    key_repr=repr(key)[:512],
                    resolver=resolver,
                    compile_s=compile_s,
                )
                self._entries[key] = entry
                telemetry.PROGRAM_REGISTRATIONS.inc(source=source)
                self._evict_locked()
                telemetry.PROGRAM_CATALOG_ENTRIES.set(
                    len(self._entries)
                )
            else:
                if resolver is not None:
                    entry.resolver = resolver
                if compile_s:
                    entry.compile_s = compile_s
                entry.last_used_s = time.time()
            return entry

    def _evict_locked(self) -> None:
        while len(self._entries) > self.max_entries:
            victim = min(
                self._entries, key=lambda k: self._entries[k].last_used_s
            )
            del self._entries[victim]
            self.evictions += 1
            telemetry.PROGRAM_EVICTIONS.inc()

    def note_hit(self, key) -> None:
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                entry.hits += 1
                entry.last_used_s = time.time()

    def note_compile_seconds(self, key, seconds: float) -> None:
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and seconds > 0:
                entry.compile_s = seconds

    # ---- lazy resolution ----------------------------------------------

    def _resolve(self, entry: ProgramEntry) -> None:
        if entry.resolved or entry.resolver is None:
            return
        entry.resolved = True  # one attempt; failures are not retried
        try:
            info = _introspect(entry.resolver())
        except Exception as e:
            entry.resolve_error = f"{type(e).__name__}: {e}"
            return
        cost = info.get("cost")
        if cost is not None:
            entry.flops = cost["flops"]
            entry.bytes_accessed = cost["bytes_accessed"]
        mem = info.get("memory")
        if mem is not None:
            entry.argument_bytes = mem["argument_bytes"]
            entry.output_bytes = mem["output_bytes"]
            entry.temp_bytes = mem["temp_bytes"]
            entry.generated_code_bytes = mem["generated_code_bytes"]
            entry.alias_bytes = mem["alias_bytes"]
        text = info.get("hlo_text")
        if text:
            entry.hlo_hash = hashlib.sha1(text.encode()).hexdigest()[:16]
            entry.hlo_lines = text.count("\n") + 1
            entry.scopes = scope_map_from_hlo(text)
            m = _MODULE_RE.match(text)
            entry.module = m.group(1) if m is not None else None
            entry.hlo_text = text[:HLO_TEXT_LIMIT]

    # ---- reads --------------------------------------------------------

    def entry_for(self, key, resolve: bool = False):
        with self._lock:
            entry = self._entries.get(key)
        if entry is not None and resolve:
            self._resolve(entry)
        return entry

    def get(self, program_id: str, resolve: bool = True):
        with self._lock:
            hit = next(
                (
                    e for e in self._entries.values()
                    if e.program_id == program_id
                ),
                None,
            )
        if hit is not None and resolve:
            self._resolve(hit)
        return hit

    def cost(self, key) -> Optional[dict]:
        """{'flops', 'bytes_accessed'} for one cached program — the
        one catalog read the executors' ``chain_cost`` routes through
        (replacing the old per-executor recompute-per-lookup path)."""
        entry = self.entry_for(key, resolve=True)
        if entry is None or entry.flops is None:
            return None
        return {
            "flops": entry.flops,
            "bytes_accessed": entry.bytes_accessed or 0.0,
        }

    def memory(self, key) -> Optional[dict]:
        """memory_analysis() HBM breakdown for one cached program."""
        entry = self.entry_for(key, resolve=True)
        if entry is None or entry.temp_bytes is None:
            return None
        return {
            "argument_bytes": entry.argument_bytes,
            "output_bytes": entry.output_bytes,
            "temp_bytes": entry.temp_bytes,
            "generated_code_bytes": entry.generated_code_bytes,
            "alias_bytes": entry.alias_bytes,
        }

    def scope_maps(self, resolve: bool = True) -> dict[str, dict[str, str]]:
        """``{module name: {instruction: scope}}``, for joining a
        captured profile whose events name their HLO module (a CPU
        trace's; a chip's carries the scope on the event itself) back
        onto plan operators. Two programs of one name (other shapes of
        one chain) share a module name and their maps are merged: where
        an instruction name collides there, both programs are the same
        operators in the same order."""
        with self._lock:
            entries = list(self._entries.values())
        out: dict[str, dict[str, str]] = {}
        for e in entries:
            if resolve:
                self._resolve(e)
            if e.module is not None:
                out.setdefault(e.module, {}).update(e.scopes)
        return out

    def snapshot(
        self, include_hlo: bool = False, resolve: bool = True,
    ) -> list[dict]:
        """JSON-safe entry list (most-used first) — the payload of
        ``GET /v1/programs``, ``system.runtime.programs`` and
        diagnostics bundles."""
        with self._lock:
            entries = list(self._entries.values())
        if resolve:
            for e in entries:
                self._resolve(e)
        entries.sort(key=lambda e: (-e.hits, e.label, e.program_id))
        return [e.to_dict(include_hlo=include_hlo) for e in entries]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            telemetry.PROGRAM_CATALOG_ENTRIES.set(0)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


#: the process-wide catalog every registration site feeds
CATALOG = ProgramCatalog()
