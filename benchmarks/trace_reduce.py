"""Raw ``.xplane.pb`` -> device busy union, idle gaps, per-program time.

The reduction is the benchmark's own so that every PR computes the same
number in the same way. It reads the trace with
``jax.profiler.ProfileData`` and nothing else of jax (no backend is
touched), and works on plain lists from there on, so the arithmetic is
tested without a trace.

Device planes are the planes named ``/device:TPU:<n>``. On each, the
line ``XLA Modules`` holds one event per execution of a compiled
program and the line ``XLA Ops`` one per operation inside it. *Busy* is
the union of the op intervals (of the module intervals where a plane
has no op line); a program's time is the sum of its module events.
Times are nanoseconds on the trace's clock, which starts at 0 when the
profiler starts; ``bench_clock_mark`` (a host annotation whose wall
time the launcher printed) ties it to the client's clock.
"""

from __future__ import annotations

import glob
import json
import os

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
MARK = "bench_clock_mark"


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(path: str) -> dict:
    """The trace as plain data: ``{"devices": {plane: {"modules":
    [(name, start, end)], "ops": [(name, start, end)]}}, "mark_ns":
    start of the clock mark or None}``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict = {}
    mark = None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    dev["modules"] = _events(line)
                elif line.name == OP_LINE:
                    dev["ops"] = _events(line)
        elif mark is None and plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARK:
                        mark = float(e.start_ns)
                        break
                if mark is not None:
                    break
    return {"devices": devices, "mark_ns": mark}


def _events(line) -> list:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def union(intervals: list, lo: float, hi: float) -> list:
    """Merged, sorted intervals clipped to [lo, hi]."""
    clipped = sorted(
        (max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi
    )
    out: list = []
    for s, e in clipped:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The idle intervals of [lo, hi] that ``busy`` (merged) leaves."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def program_name(name: str) -> str:
    """``jit_counted(9246779623455092039)`` -> ``jit_counted_9246779623455092039``:
    the number in brackets is the program's fingerprint, the only thing
    that tells two programs of one Python function apart."""
    return name.strip().replace("(", "_").replace(")", "")


def reduce(trace: dict, lo_ns: float, hi_ns: float,
           in_flight: list | None = None) -> dict:
    """Everything the per-layer readers take from a trace, for the
    window [lo_ns, hi_ns] of the trace's clock.

    ``in_flight`` is the client's timeline on the same clock:
    ``[(start_ns, end_ns, label)]``. An idle gap is charged to the
    label of the statement in flight through most of it (the latest
    started, where several are), or to ``no_statement_in_flight``.
    """
    window_s = (hi_ns - lo_ns) / 1e9
    per_dev_busy, programs, executions = [], {}, 0
    idle_by: dict = {}
    for dev in trace["devices"].values():
        spans = dev["ops"] or dev["modules"]
        busy = union([(s, e) for _, s, e in spans], lo_ns, hi_ns)
        per_dev_busy.append(sum(e - s for s, e in busy) / 1e9)
        for name, s, e in dev["modules"]:
            if e > lo_ns and s < hi_ns:
                executions += 1
                key = program_name(name)
                programs[key] = programs.get(key, 0.0) + (
                    min(e, hi_ns) - max(s, lo_ns)) / 1e9
        for s, e in gaps(busy, lo_ns, hi_ns):
            label = _label(in_flight or [], s, e)
            idle_by[label] = idle_by.get(label, 0.0) + (e - s) / 1e9
    n = max(1, len(per_dev_busy))
    top = sorted(programs.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(((k, v / n) for k, v in idle_by.items()),
                  key=lambda kv: -kv[1])[:10]
    return {
        "devices": len(per_dev_busy),
        "window_s": window_s,
        "busy_s": sum(per_dev_busy) / n if per_dev_busy else 0.0,
        "executions": executions,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [[k, v] for k, v in idle],
    }


def _label(in_flight: list, s: float, e: float) -> str:
    best, best_cover, best_start = "no_statement_in_flight", 0.0, -1.0
    for fs, fe, label in in_flight:
        cover = min(e, fe) - max(s, fs)
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover and fs > best_start):
            best, best_cover, best_start = label, cover, fs
    if best_cover * 2 < (e - s):
        return "no_statement_in_flight"
    return "during_" + best


def for_window(trace_dir: str, mark_wall_ns: int | None, ctx,
               timeline_path: str | None = None) -> dict | None:
    """The reduction of a run's raw trace over its measured window,
    first send to last reply, with the client's statements as the
    timeline idle gaps are charged to. None where there is no trace or
    no device plane in it (a CPU rehearsal)."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    trace = load(path)
    if not trace["devices"]:
        return None
    sts = [s for s in ctx.statements if s.sent_s is not None]
    if trace["mark_ns"] is None or mark_wall_ns is None or not sts:
        # without the mark the window could only be guessed as first to
        # last device operation, which leaves out the idle time at both
        # ends: device.idle_share would read low and say nothing of it
        raise RuntimeError(
            f"the trace holds no {MARK} annotation (or the launcher "
            f"printed no wall time for it): the client's window cannot "
            f"be placed on the trace's clock")
    # wall clock -> trace clock
    shift = trace["mark_ns"] - mark_wall_ns

    def to_trace(mono_s: float) -> float:
        return ctx.t0_wall_ns + (mono_s - ctx.t0) * 1e9 + shift

    timeline = [(to_trace(s.sent_s), to_trace(s.done_s), label_of(s))
                for s in sts]
    lo = min(t[0] for t in timeline)
    hi = max(t[1] for t in timeline)
    out = reduce(trace, lo, hi, timeline)
    out["xplane"] = path
    if timeline_path:
        with open(timeline_path, "w") as fh:
            json.dump({"lo_ns": lo, "hi_ns": hi, "timeline": timeline,
                       "mark_ns": trace["mark_ns"]}, fh)
    return out


def label_of(st) -> str:
    tail = "_".join(str(v) for v in st.params.values())
    tail = "".join(c if c.isalnum() else "_" for c in tail)[:24]
    return f"{st.template}_{tail}" if st.cls == "long" else st.template
