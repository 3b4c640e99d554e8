"""On-demand device profiling: device time by plan operator, kernel and
primitive, inside the compiled programs.

The roofline profiler (PR 7) and flight recorder (PR 12) stop at the
*operator* boundary — but a fused chain is ONE XLA program, so "where
does q03's time go" was unanswerable below the chain. This module
closes that gap:

1. every instruction of every program is traced under the scopes of
   one grammar (``exec/kernels.py``: ``op<i>:<NodeType>`` /
   ``op:<NodeType>``, ``k:<kernel>``, ``s:<site>``), which XLA stamps
   into the instruction's ``op_name`` metadata (fusions included);
2. :class:`Capture` runs ``jax.profiler.trace`` around a window of
   device work;
3. the trace is folded onto those scopes by what it holds itself:

   * a chip's raw trace (``*.xplane.pb``) carries every instruction's
     ``op_name`` on its ``XLA Ops`` event's metadata (``tf_op``) —
     :func:`attribute_device` decodes the device planes by the protobuf
     wire format (stdlib only; ``jax.profiler.ProfileData`` shows an
     event's own stats, not its metadata's) and charges each event's
     **self time** (an event less the events nested in it on its line:
     a ``while`` holds its body's) once to its operator, its innermost
     kernel and its primitive. No catalog, no HLO text: an event's
     metadata names its program (``program_id``) by itself;
   * a CPU trace holds no ``tf_op``; its Chrome-trace events name the
     HLO module and instruction, and :func:`attribute` joins them to
     the program catalog's instruction→scope map *of that module*
     (:func:`program_catalog.scope_map_from_hlo`).

Triggers: the ``kernel_profile`` session property (ON / AUTO),
``POST /v1/profile?duration_ms=`` on coordinator and workers, and —
via AUTO — the slow-query log. Captures are process-exclusive
(``jax.profiler.start_trace`` raises if one is active), so a nested
Capture degrades to a no-op rather than poisoning the outer one.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
import tempfile
import threading
import time

from trino_tpu import program_catalog, telemetry

__all__ = [
    "Capture", "capture_for", "parse_trace_dir", "attribute",
    "scopes_of", "device_planes", "attribute_device",
]

#: process-wide exclusivity: jax allows one active trace per process
_capture_lock = threading.Lock()


# ---- the scope grammar -------------------------------------------------------

_OP_RE = re.compile(r"op\d*:([A-Za-z_]\w*)")
#: jax primitives that are a prefix scan (the last component of an
#: instruction's op_name is the primitive it was lowered from)
_SCANS = ("cumsum", "cumlogsumexp", "cummax", "cummin", "cumprod",
          "associative_scan")


def scopes_of(op_name: str) -> dict:
    """``op_name`` (HLO metadata; a device trace's ``tf_op``, which is
    ``<op_name>:<op_type>``) read under the grammar of
    ``exec/kernels.py``: ``operator`` (the node type of the ``op…:``
    component), ``scope`` (that component whole), ``kernel`` (innermost
    ``k:``), ``site`` (innermost ``s:``) — each None where there is
    none — and ``primitive``: ``gather``, ``scatter``, ``sort``,
    ``scan`` or ``other``, from the last component."""
    if op_name.rfind(":") > op_name.rfind("/"):
        op_name = op_name[:op_name.rfind(":")]
    comps = op_name.split("/")
    out = {"operator": None, "scope": None, "kernel": None, "site": None}
    for comp in comps[:-1]:
        m = _OP_RE.fullmatch(comp)
        if m is not None:
            out["operator"], out["scope"] = m.group(1), comp
        elif comp.startswith("k:"):
            out["kernel"] = comp[2:]
        elif comp.startswith("s:"):
            out["site"] = comp[2:]
    last = comps[-1]
    if last.startswith("scatter"):
        out["primitive"] = "scatter"
    elif last in ("gather", "sort"):
        out["primitive"] = last
    elif last in _SCANS or last.startswith("reduce_window"):
        out["primitive"] = "scan"
    else:
        out["primitive"] = "other"
    return out


# ---- a chip's raw trace: the device planes of an .xplane.pb ------------------
#
# XSpace{1: planes}; XPlane{2: name, 3: lines, 4: event_metadata (map
# id -> XEventMetadata), 5: stat_metadata (map id -> XStatMetadata)};
# XLine{2: name, 3: timestamp_ns, 4: events}; XEvent{1: metadata_id,
# 2: offset_ps, 3: duration_ps}; XEventMetadata{1: id, 2: name, 5:
# stats}; XStat{1: metadata_id, 2: double, 3: uint64, 4: int64, 5: str,
# 7: ref}; XStatMetadata{1: id, 2: name}.

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of one message: a varint's number, a
    length-delimited field's ``(start, end)`` in ``buf``, a fixed
    field's bytes."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")
        yield key >> 3, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value (field 2) of one protobuf map entry."""
    for f, v in _fields(buf, *span):
        if f == 2:
            return v
    return None


def device_planes(path: str) -> list[dict]:
    """The device planes of a raw trace: ``[{"name", "ops": [(start_ps,
    end_ps, metadata id)] of the ``XLA Ops`` line, "metadata": {id:
    {"name", "tf_op", "program_id"}}}]``. Other planes are skipped by
    their length."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    planes = []
    for f, span in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        parts = list(_fields(buf, *span))
        name = next((_text(buf, v) for f2, v in parts if f2 == 2), "")
        if not name.startswith(DEVICE_PREFIX):
            continue
        stat_names = {}
        for f2, v in parts:
            if f2 == 5 and (val := _map_value(buf, v)) is not None:
                sm = dict(_fields(buf, *val))
                stat_names[sm.get(1)] = _text(buf, sm[2]) if 2 in sm else ""
        metadata = {}
        for f2, v in parts:
            if f2 != 4 or (val := _map_value(buf, v)) is None:
                continue
            md = {"name": "", "tf_op": None, "program_id": None}
            mid = None
            for f3, v3 in _fields(buf, *val):
                if f3 == 1:
                    mid = v3
                elif f3 == 2:
                    md["name"] = _text(buf, v3)
                elif f3 == 5:
                    stat = dict(_fields(buf, *v3))
                    key = stat_names.get(stat.get(1))
                    if key == "tf_op" and 5 in stat:
                        md["tf_op"] = _text(buf, stat[5])
                    elif key == "program_id":
                        md["program_id"] = stat.get(3, stat.get(4))
            metadata[mid] = md
        ops = []
        for f2, v in parts:
            if f2 != 3:
                continue
            line = list(_fields(buf, *v))
            if next((_text(buf, x) for f3, x in line if f3 == 2), "") != OP_LINE:
                continue
            for f3, x in line:
                if f3 == 4:
                    ev = dict(_fields(buf, *x))
                    start = ev.get(2, 0)
                    ops.append((start, start + ev.get(3, 0), ev.get(1)))
        planes.append({"name": name, "ops": ops, "metadata": metadata})
    return planes


def self_times(ops: list) -> list:
    """``[(self_ps, metadata id)]`` of one line's ``(start, end, id)``
    events: an event's duration less the events nested in it."""
    out, stack = [], []
    for start, end, mid in sorted(ops, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][0] -= end - start
        stack.append((end, len(out)))
        out.append([end - start, mid])
    return [(max(ps, 0), mid) for ps, mid in out]


def _ranked(d: dict) -> dict:
    return {k: round(v, 1) for k, v in sorted(d.items(), key=lambda kv: -kv[1])}


def attribute_device(planes: list[dict]) -> dict | None:
    """A chip's trace folded onto the scope grammar: microseconds of
    self time by ``scopes`` (the ``op…:`` component), ``operators``,
    ``kernels`` and ``primitives`` — a device's mean where the trace
    holds several planes — and ``unscoped_us``, the time under no
    operator scope. None where no event's metadata carries ``tf_op``
    (a CPU trace: the caller takes the catalog's map instead)."""
    if not any(md["tf_op"] for p in planes for md in p["metadata"].values()):
        return None
    scopes: dict = {}
    axes = {"operators": {}, "kernels": {}, "primitives": {}}
    unscoped = 0.0
    events = matched = 0
    parsed: dict = {}
    for plane in planes:
        for ps, mid in self_times(plane["ops"]):
            events += 1
            tf_op = (plane["metadata"].get(mid) or {}).get("tf_op") or ""
            sc = parsed.get(tf_op)
            if sc is None:
                sc = parsed[tf_op] = scopes_of(tf_op)
            us = ps / 1e6 / len(planes)
            if sc["scope"] is None:
                unscoped += us
            else:
                matched += 1
                scopes[sc["scope"]] = scopes.get(sc["scope"], 0.0) + us
            for axis, key in (("operators", sc["operator"] or "unscoped"),
                              ("kernels", sc["kernel"] or "none"),
                              ("primitives", sc["primitive"])):
                axes[axis][key] = axes[axis].get(key, 0.0) + us
    return {
        "scopes": _ranked(scopes),
        **{axis: _ranked(by) for axis, by in axes.items()},
        "attributed_us": round(sum(scopes.values()), 1),
        "unattributed_us": round(unscoped, 1),
        "unscoped_us": round(unscoped, 1),
        "events": events,
        "matched_events": matched,
        "devices": len(planes),
    }


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


# ---- a CPU trace: Chrome-trace events joined to the catalog's map ------------


def parse_trace_dir(trace_dir: str) -> list[dict]:
    """Complete ("X") events from every ``*.trace.json.gz`` the
    profiler wrote under ``trace_dir``. Each event keeps its name,
    duration (µs), and any ``hlo_op`` / ``hlo_module`` arg."""
    events: list[dict] = []
    pattern = os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz"
    )
    for path in sorted(glob.glob(pattern)):
        try:
            with gzip.open(path, "rt") as f:
                doc = json.load(f)
        except Exception:
            continue
        for ev in doc.get("traceEvents", []) or []:
            if ev.get("ph") != "X" or "dur" not in ev:
                continue
            args = ev.get("args") or {}
            events.append({
                "name": ev.get("name", ""),
                "dur_us": float(ev["dur"]),
                "hlo_op": args.get("hlo_op"),
                "hlo_module": args.get("hlo_module"),
            })
    return events


def attribute(
    events: list[dict], scope_maps: dict[str, dict[str, str]] | None = None
) -> dict:
    """Fold Chrome-trace event durations onto named plan-operator
    scopes, for a trace whose events carry no ``tf_op`` (a CPU's).

    An event belongs to an HLO instruction when its ``hlo_op`` arg (or
    its name) appears in the instruction→scope map of the module the
    event names (``hlo_module``; ``scope_maps`` is the catalog's
    ``{module: {instruction: scope}}``); device work that maps to no
    named scope — glue ops XLA emitted outside any operator's
    lowering, other processes' modules — lands in ``unscoped_us`` so
    the totals stay honest. The kernel and primitive axes are empty
    here: the map keeps an instruction's operator scope only."""
    if scope_maps is None:
        scope_maps = program_catalog.CATALOG.scope_maps()
    scopes: dict[str, float] = {}
    unscoped = 0.0
    matched_events = 0
    for ev in events:
        # trace instruction names may carry a "%" sigil the HLO text
        # form does not
        instr = (ev.get("hlo_op") or ev.get("name") or "").lstrip("%")
        scope = scope_maps.get(ev.get("hlo_module") or "", {}).get(instr)
        if scope is None:
            m = program_catalog._SCOPE_RE.search(ev.get("name") or "")
            if m is not None:
                scope = m.group(0)
        if scope is not None:
            scopes[scope] = scopes.get(scope, 0.0) + ev["dur_us"]
            matched_events += 1
        elif ev.get("hlo_op"):
            # only count device-side HLO work as unscoped; plain
            # host python events would drown the denominator
            unscoped += ev["dur_us"]
    operators: dict[str, float] = {}
    for scope, us in scopes.items():
        op = scope.split(":", 1)[1]
        operators[op] = operators.get(op, 0.0) + us
    if unscoped:
        operators["unscoped"] = unscoped
    return {
        "scopes": _ranked(scopes),
        "operators": _ranked(operators),
        "kernels": {},
        "primitives": {},
        "attributed_us": round(sum(scopes.values()), 1),
        "unattributed_us": round(unscoped, 1),
        "unscoped_us": round(unscoped, 1),
        "events": len(events),
        "matched_events": matched_events,
    }


def attribute_dir(trace_dir: str) -> dict:
    """The attribution of one captured trace, by what it holds: its
    device planes' ``tf_op`` where there are any, else its Chrome-trace
    events against the catalog."""
    xplane = find_xplane(trace_dir)
    if xplane is not None:
        out = attribute_device(device_planes(xplane))
        if out is not None:
            return out
    return attribute(parse_trace_dir(trace_dir))


class Capture:
    """Context manager around one ``jax.profiler.trace`` window.

    ``active`` is False when another capture already holds the process
    lock (or the profiler fails to start) — the body still runs, the
    capture is just a no-op and ``summary()`` returns None."""

    def __init__(self, trigger: str = "manual"):
        self.trigger = trigger
        self.active = False
        self._dir: str | None = None
        self._summary: dict | None = None

    def __enter__(self):
        # the hold legitimately spans __enter__→__exit__: released in
        # __exit__'s finally, or below when the profiler fails to start
        if not _capture_lock.acquire(blocking=False):  # lint: disable=LCK001
            return self
        try:
            import jax

            self._dir = tempfile.mkdtemp(prefix="trino-kernel-prof-")
            jax.profiler.start_trace(self._dir)
            self.active = True
            telemetry.KERNEL_PROFILES.inc(trigger=self.trigger)
        except Exception:
            self._cleanup()
            _capture_lock.release()
        return self

    def __exit__(self, *exc):
        if not self.active:
            return False
        try:
            import jax

            jax.profiler.stop_trace()
            self._summary = attribute_dir(self._dir)
            self._summary["trigger"] = self.trigger
        except Exception:
            self._summary = None
        finally:
            self.active = False
            self._cleanup()
            _capture_lock.release()
        return False

    def _cleanup(self) -> None:
        if self._dir:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None

    def summary(self) -> dict | None:
        return self._summary


def capture_for(duration_ms: float, trigger: str = "endpoint") -> dict:
    """Blocking wall-clock capture (the ``POST /v1/profile`` body):
    trace whatever device work runs during the window, attribute it.
    Returns ``{"error": ...}`` instead of raising when another capture
    holds the process lock."""
    duration_ms = max(float(duration_ms), 1.0)
    with Capture(trigger=trigger) as cap:
        if not cap.active:
            return {"error": "profiler busy: another capture is active"}
        time.sleep(duration_ms / 1000.0)
    out = cap.summary() or {"error": "capture produced no trace"}
    if "error" not in out:
        out["duration_ms"] = duration_ms
    return out
