"""Device idle time by what the host was doing: the program's spans
(``trino_tpu/telemetry.py``: every live span is a
``jax.profiler.TraceAnnotation`` carrying its ``query_id``) read off the
host planes of the same raw trace as the device's operations.

args: ``quantity``, ``span``
  "idle_share_in"            100 * idle time charged to spans named
                             ``span`` / all idle time of the window
  "idle_share_unattributed"  100 * idle time that no span of the program
                             other than ``statement`` covers / all idle
                             time: the check that the spans are complete
Nothing where the run has no device trace. Where it has one (a traced
run only gets here with the clock mark in it: ``trace_reduce.for_window``)
and the trace holds no span of the program, the run fails: a change that
drops or renames the spans must not make these metrics vanish.

How it reads the trace. ``ctx.trace["xplane"]`` is the run's raw
``.xplane.pb``; ``timeline.json`` beside the trace directory holds the
window's ``lo_ns``/``hi_ns`` on the trace's clock, as
``trace_reduce.for_window`` placed it by the clock mark. The idle
intervals are recomputed as ``trace_reduce`` computes them (``union`` of
each device plane's op intervals, then ``gaps``). The program's spans
are the events of the ``/host:`` planes that carry a ``query_id``; one
line is one thread, and a span's depth is the number of such events
around it on its line. Every instant of an idle interval is charged to
one span among those open at that instant: a span that does work before
one that waits (``statement``, ``queued``, ``runner_wait``,
``task_queue_wait`` hold no device), then the innermost (deepest on its
thread), then the latest started (``trace_reduce._label``'s
tie-break). With no span open, or ``statement`` alone, the instant is
unattributed: host work that has no span yet, or no statement in
flight. (An interval is cut at the span boundaries inside it and not
charged whole to the span covering most of it, as ``_label`` charges a
gap to a statement: the gap between two statements of a closed loop is
some 15 ms crossed by ten spans none of which covers half, and charged
whole it read 77 % unattributed in ``sf1_power`` — my chip run, PR 25.)

A later PR adds a metric over another span as one data file: a
``metrics/<name>.json`` with ``"reader": "host_spans"`` and ``"args":
{"quantity": "idle_share_in", "span": "<name>"}``; over a field of
``GET /v1/query`` (``trino_tpu/server/coordinator.py`` puts every
span's total there as ``<name>_ms``) as one data file with ``"reader":
"query_list"`` and ``"args": {"field": "<name>_ms"}``, which fails the
run where under nine tenths of the window's statements carry the field.
"""

from __future__ import annotations

import json
import os

import trace_reduce

#: spans that wait or contain: they hold no device, so a span that does
#: work is charged before them
WAITS = ("statement", "queued", "runner_wait", "task_queue_wait")
UNATTRIBUTED = "unattributed"


def host_spans(path: str) -> list:
    """The program's spans in a raw trace: ``[(name, start_ns, end_ns,
    depth, query_id)]`` from every event of a ``/host:`` plane that
    carries a ``query_id``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = []
            for e in line.events:
                qid = dict(e.stats).get("query_id")
                if qid is not None:
                    start = float(e.start_ns)
                    events.append(
                        (e.name, start, start + float(e.duration_ns),
                         str(qid)))
            out.extend(with_depth(events))
    return out


def with_depth(events: list) -> list:
    """``[(name, start, end, query_id)]`` of one thread ->
    ``[(name, start, end, depth, query_id)]``."""
    out, open_ends = [], []
    for name, s, e, qid in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while open_ends and open_ends[-1] <= s:
            open_ends.pop()
        out.append((name, s, e, len(open_ends), qid))
        open_ends.append(e)
    return out


def charge(idle: list, spans: list) -> dict:
    """Idle nanoseconds by span name (or ``UNATTRIBUTED``): every
    instant of every idle interval ``(s, e)`` goes to one span (module
    docstring)."""
    out: dict = {}
    spans = sorted(spans, key=lambda sp: sp[1])
    nxt, active = 0, []
    for s, e in sorted(idle):
        # one sweep: the spans that started before the interval ends
        # and have not ended before it starts
        while nxt < len(spans) and spans[nxt][1] < e:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[2] > s]
        # the interval cut where a span starts or ends inside it
        cuts = sorted({s, e} | {t for sp in active for t in sp[1:3]
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            best, best_rank = UNATTRIBUTED, None
            for name, fs, fe, depth, _qid in active:
                if fs <= a and fe >= b:
                    rank = (name not in WAITS, depth, fs)
                    if best_rank is None or rank > best_rank:
                        best, best_rank = name, rank
            if best == "statement":
                best = UNATTRIBUTED
            out[best] = out.get(best, 0.0) + (b - a)
    return out


def idle_intervals(trace: dict, lo: float, hi: float) -> list:
    """Every device's idle intervals of the window, as
    ``trace_reduce.reduce`` finds them."""
    out = []
    for dev in trace["devices"].values():
        ops = dev["ops"] or dev["modules"]
        busy = trace_reduce.union([(s, e) for _, s, e in ops], lo, hi)
        out.extend(trace_reduce.gaps(busy, lo, hi))
    return out


def find_timeline(xplane: str) -> str | None:
    """``timeline.json`` beside the trace directory that holds
    ``xplane`` (``<work>/trace/plugins/profile/<run>/<file>``)."""
    d = os.path.dirname(xplane)
    for _ in range(6):
        cand = os.path.join(d, "timeline.json")
        if os.path.exists(cand):
            return cand
        d = os.path.dirname(d)
    return None


def idle_by_span(ctx) -> dict | None:
    """``charge`` over the run's raw trace, once a run (kept on ``ctx``:
    several metrics read it). None where there is no device trace."""
    tr = ctx.trace
    if tr is None or not tr.get("devices") or not tr.get("xplane"):
        return None
    cached = getattr(ctx, "_idle_by_span", None)
    if cached is None:
        timeline = find_timeline(tr["xplane"])
        if timeline is None:
            raise RuntimeError(
                "no timeline.json beside the trace: the window cannot be "
                "placed on the trace's clock")
        with open(timeline) as fh:
            window = json.load(fh)
        devices = trace_reduce.load(tr["xplane"])
        if not devices["devices"]:
            return None
        spans = host_spans(tr["xplane"])
        if not spans:
            raise RuntimeError(
                "the device trace holds no span of the program (no host "
                "event with a query_id): the idle time cannot be charged")
        idle = idle_intervals(devices, window["lo_ns"], window["hi_ns"])
        cached = charge(idle, spans)
        ctx._idle_by_span = cached
    return cached or None


def share(by_span: dict, quantity: str, span: str | None = None):
    total = sum(by_span.values())
    if total <= 0:
        return None
    if quantity == "idle_share_in":
        return 100.0 * by_span.get(span, 0.0) / total
    if quantity == "idle_share_unattributed":
        return 100.0 * by_span.get(UNATTRIBUTED, 0.0) / total
    raise ValueError(f"unknown quantity {quantity!r}")


def read(ctx, quantity, span=None):
    by_span = idle_by_span(ctx)
    if by_span is None:
        return None
    return share(by_span, quantity, span)
