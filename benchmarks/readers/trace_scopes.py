"""Device time inside the programs: by plan operator, by kernel, by
primitive — from the scopes the device trace carries itself.

The program traces every instruction under ``jax.named_scope``s of one
grammar (``trino_tpu/exec/kernels.py``): ``op<i>:<NodeType>`` (a
chain's position) or ``op:<NodeType>`` (a program that is one
operator), ``k:<kernel>``, ``s:<site>``. XLA keeps the path as the
instruction's ``op_name``, and a TPU's trace hands it back as ``tf_op``
on the metadata of every ``XLA Ops`` event (``xplane_meta.py``).

args: ``quantity``, ``axis``, ``cls``
  "ms_per_stmt"     self time of the window's op events whose class on
                    ``axis`` is ``cls`` (a name, or a list of names
                    whose times add), a device's mean over the planes,
                    / statements. Axes and their classes:
                      "operator"   the NodeType of the event's ``op…:``
                                   component, else "unscoped"
                      "kernel"     its innermost ``k:``, else "none"
                      "primitive"  "gather", "scatter", "sort", "scan"
                                   (cumsum, cumlogsumexp, cummax,
                                   cummin, cumprod, reduce_window*,
                                   associative_scan), else "other":
                                   the last component of ``tf_op``
  "unscoped_share"  100 * self time under no operator scope / all self
                    time: the check that the program's scopes are
                    complete
An event's **self time** is its time less the events nested in it on
its line (a ``while`` holds its body's), so every nanosecond of the
busy union is charged once on each axis and an axis' classes add up to
``kernels.busy_ms_per_stmt``.

Nothing where the run has no device trace. Where it has device planes
and no event's metadata carries ``tf_op``, the run fails: a metric must
not vanish. On a program without the ``k:`` and ``s:`` scopes (the
parent of the PR that brought them) it reads what is there: the
primitive axis whole, the operator axis with "unscoped" large.

Beside ``timeline.json`` it writes ``scopes.json``: one row a (program
— ``trace_reduce.program_name`` —, template in flight, operator,
kernel, site, primitive, source, category — XLA's ``hlo_category``: a
row under no scope whose category is ``reduce-window`` is a ``cumsum``
that jax lowers through a cached function and XLA rewrites, which
leaves it no ``tf_op`` —) with ``self_ms`` (a device's mean over the
window), ``executions`` of the program under that template,
``ms_per_run``, ``events`` and ``bytes_per_run`` (XLA's
``bytes_accessed``), dearest first: the table a ``perf_opt`` builder
reads instead of timing a program's pieces alone.

A later PR adds a class as one data file: a ``metrics/<name>.json``
with ``"reader": "trace_scopes", "args": {"quantity": "ms_per_stmt",
"axis": "kernel", "cls": "gather_rows"}``.

A traced run keeps its raw trace, so a cell that lists none of these
metrics is reduced after the run by hand (``sf5_power`` and
``sf5_mesh4_power`` list none: each cell's accepted test pins the list
of metrics that name it — PERF.md, Open questions):

    python3 benchmarks/readers/trace_scopes.py .bench_work/<cell>

writes the run's ``scopes.json`` and prints the three axes a statement,
``unscoped_share`` and the device's idle time by span
(``host_spans.charge``) as one JSON line.
"""

from __future__ import annotations

import json
import os
import re
import sys

if __name__ == "__main__":  # run by path: the benchmark's modules are one up
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import trace_reduce
import xplane_meta

UNSCOPED = "unscoped"
AXES = ("operator", "kernel", "primitive")
SCANS = ("cumsum", "cumlogsumexp", "cummax", "cummin", "cumprod",
         "associative_scan")

_OP = re.compile(r"op\d*:([A-Za-z_]\w*)")


def classify(tf_op: str | None) -> dict:
    """``tf_op`` -> ``{"operator", "kernel", "site", "primitive"}``
    under the grammar of the module docstring."""
    path = tf_op or ""
    if path.rfind(":") > path.rfind("/"):
        path = path[:path.rfind(":")]  # the ``:<op type>`` tail
    comps = path.split("/")
    out = {"operator": UNSCOPED, "kernel": "none", "site": None}
    for comp in comps[:-1]:
        m = _OP.fullmatch(comp)
        if m is not None:
            out["operator"] = m.group(1)
        elif comp.startswith("k:"):
            out["kernel"] = comp[2:]
        elif comp.startswith("s:"):
            out["site"] = comp[2:]
    last = comps[-1]
    if last.startswith("scatter"):
        out["primitive"] = "scatter"
    elif last in ("gather", "sort"):
        out["primitive"] = last
    elif last in SCANS or last.startswith("reduce_window"):
        out["primitive"] = "scan"
    else:
        out["primitive"] = "other"
    return out


def self_times(events: list) -> list:
    """``[(self_ns, event)]`` of one line's ``(name, start, end)``
    events: an event's duration less the events nested in it."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    own = [e - s for _, s, e in order]
    stack: list = []  # (end, index) of the events open at this start
    for i, (_, s, e) in enumerate(order):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= e - s
        stack.append((e, i))
    return [(max(ns, 0.0), ev) for ns, ev in zip(own, order)]


def in_window(events: list, lo: float, hi: float) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def reduce(trace: dict, metadata: dict, lo: float, hi: float,
           timeline: list) -> dict:
    """``{"axes": {axis: {class: self ns, a device's mean}}, "total_ns",
    "rows": [...]}`` of ``trace_reduce.load``'s planes over [lo, hi],
    events joined to ``xplane_meta.device_metadata`` by (program id of
    the module around them, name)."""
    axes: dict = {a: {} for a in AXES}
    rows: dict = {}
    runs: dict = {}
    n_planes = max(1, len(trace["devices"]))
    flights = sorted((s, e, label) for s, e, label in timeline)
    total = 0.0
    for plane, dev in trace["devices"].items():
        meta = metadata.get(plane, {})
        modules = sorted(
            (s, e, (trace_reduce.program_name(n),
                    xplane_meta.program_id_of(n), _flight(flights, s, e)))
            for n, s, e in in_window(dev["modules"], lo, hi))
        for _, _, (program, _, template) in modules:
            runs[(program, template)] = runs.get((program, template), 0) + 1
        at = 0
        parsed: dict = {}
        for ns, (name, s, _e) in self_times(in_window(dev["ops"], lo, hi)):
            # ops come by start: the module around one is the last that
            # started before it and has not ended
            while at + 1 < len(modules) and modules[at + 1][0] <= s:
                at += 1
            program, pid, template = (
                modules[at][2] if modules and modules[at][0] <= s
                < modules[at][1] else (None, None, None))
            md = meta.get((pid, name)) or {}
            cls = parsed.get(md.get("tf_op"))
            if cls is None:
                cls = parsed[md.get("tf_op")] = classify(md.get("tf_op"))
            total += ns
            for axis in AXES:
                by = axes[axis]
                by[cls[axis]] = by.get(cls[axis], 0.0) + ns
            key = (program, template, cls["operator"], cls["kernel"],
                   cls["site"], cls["primitive"], md.get("source"),
                   md.get("hlo_category"))
            row = rows.get(key)
            if row is None:
                row = rows[key] = [0.0, 0, 0]
            row[0] += ns
            row[1] += 1
            row[2] += md.get("bytes_accessed") or 0
    table = []
    for key, (ns, events, nbytes) in rows.items():
        program, template = key[0], key[1]
        n_runs = runs.get((program, template), 0) / n_planes
        table.append({
            "program": program, "template": template, "operator": key[2],
            "kernel": key[3], "site": key[4], "primitive": key[5],
            "source": key[6], "category": key[7],
            "self_ms": ns / n_planes / 1e6,
            "executions": n_runs,
            "ms_per_run": ns / n_planes / 1e6 / n_runs if n_runs else None,
            "events": events,
            "bytes_per_run": nbytes / n_planes / n_runs if n_runs else None,
        })
    table.sort(key=lambda r: -r["self_ms"])
    return {
        "axes": {a: {k: v / n_planes for k, v in by.items()}
                 for a, by in axes.items()},
        "total_ns": total / n_planes,
        "rows": table,
    }


def _flight(flights: list, s: float, e: float) -> str | None:
    """The template (``trace_reduce.label_of``) of the statement in
    flight through most of [s, e], the latest started where several."""
    best, cover = None, 0.0
    for fs, fe, label in flights:
        c = min(e, fe) - max(s, fs)
        if c > 0 and c >= cover:
            best, cover = label, c
    return best


def _sibling(name: str):
    """A sibling reader (readers are loaded by path, not as a package)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("_reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def loaded(ctx) -> dict:
    """``trace_reduce.load`` of the run's raw trace, once a run (kept on
    ``ctx``: reading a window's trace takes seconds, and
    ``idle_under_span`` wants the same planes)."""
    trace = getattr(ctx, "_loaded_trace", None)
    if trace is None:
        trace = ctx._loaded_trace = trace_reduce.load(ctx.trace["xplane"])
    return trace


def scopes(ctx) -> dict | None:
    """``reduce`` over the run's raw trace, once a run (kept on ``ctx``:
    several metrics read it), with ``scopes.json`` written beside
    ``timeline.json``. None where there is no device trace."""
    tr = ctx.trace
    if tr is None or not tr.get("devices") or not tr.get("xplane"):
        return None
    cached = getattr(ctx, "_trace_scopes", None)
    if cached is not None:
        return cached
    timeline = _sibling("host_spans").find_timeline(tr["xplane"])
    if timeline is None:
        raise RuntimeError(
            "no timeline.json beside the trace: the window cannot be "
            "placed on the trace's clock")
    with open(timeline) as fh:
        window = json.load(fh)
    trace = loaded(ctx)
    if not trace["devices"]:
        return None
    metadata = xplane_meta.device_metadata(tr["xplane"])
    if not any(md.get("tf_op") for plane in metadata.values()
               for md in plane.values()):
        raise RuntimeError(
            "the trace has device planes and no event metadata carries "
            "tf_op: device time cannot be charged to the program's scopes")
    out = reduce(trace, metadata, window["lo_ns"], window["hi_ns"],
                 window["timeline"])
    out["statements"] = len(ctx.statements)
    with open(os.path.join(os.path.dirname(timeline), "scopes.json"),
              "w") as fh:
        json.dump({"statements": out["statements"],
                   "devices": len(trace["devices"]),
                   "total_ms": out["total_ns"] / 1e6,
                   "axes_ms": {a: {k: v / 1e6 for k, v in sorted(
                       by.items(), key=lambda kv: -kv[1])}
                       for a, by in out["axes"].items()},
                   "rows": out["rows"]}, fh, indent=1)
    ctx._trace_scopes = out
    return out


def read(ctx, quantity, axis=None, cls=None):
    sc = scopes(ctx)
    if sc is None or sc["total_ns"] <= 0:
        return None
    if quantity == "unscoped_share":
        return 100.0 * sc["axes"]["operator"].get(UNSCOPED, 0.0) / sc["total_ns"]
    if quantity == "ms_per_stmt":
        if axis not in AXES:
            raise ValueError(f"unknown axis {axis!r}")
        if not sc["statements"]:
            return None
        names = [cls] if isinstance(cls, str) else list(cls)
        ns = sum(sc["axes"][axis].get(n, 0.0) for n in names)
        return ns / 1e6 / sc["statements"]
    raise ValueError(f"unknown quantity {quantity!r}")


def main(workdir: str) -> int:
    """``scopes.json`` and a summary line for a finished traced run's
    work directory (``.bench_work/<cell>``)."""
    from types import SimpleNamespace

    xplane = trace_reduce.find_xplane(os.path.join(workdir, "trace"))
    if xplane is None:
        print(f"no raw trace under {workdir}/trace", file=sys.stderr)
        return 2
    with open(os.path.join(workdir, "statements.jsonl")) as fh:
        n = sum(1 for line in fh if line.strip())
    ctx = SimpleNamespace(trace={"devices": 1, "xplane": xplane},
                          statements=[None] * n)
    sc = scopes(ctx)
    if sc is None:
        print("the trace holds no device plane", file=sys.stderr)
        return 3
    try:
        idle = _sibling("host_spans").idle_by_span(ctx) or {}
    except RuntimeError as e:  # a trace without the program's spans
        print(f"idle time not charged: {e}", file=sys.stderr)
        idle = {}
    idle_ns = sum(idle.values())
    print(json.dumps({
        "statements": n,
        "busy_ms_per_stmt": sc["total_ns"] / 1e6 / n,
        "unscoped_share": read(ctx, "unscoped_share"),
        "ms_per_stmt": {a: {k: v / 1e6 / n for k, v in sorted(
            by.items(), key=lambda kv: -kv[1])}
            for a, by in sc["axes"].items()},
        "idle_share_by_span": {k: 100.0 * v / idle_ns for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])} if idle_ns else {},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
