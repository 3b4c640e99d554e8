"""Device idle time under a span *and everything nested in it*.

``readers/host_spans.py`` charges every instant of an idle interval to
the innermost span open at it, so a span that has children keeps only
what lies between them: ``epilogue`` (``trino_tpu/engine.py``) holds one
child a recorder, and by its own name reads the residue. This reader
gives the whole: every span of a statement that lies inside a span named
``span`` of the same statement (same ``query_id``, its interval within
the other's) is charged as ``span``, then ``host_spans.charge`` runs as
it does.

args: ``span``
  100 * idle time of the window charged to ``span`` or a span nested in
  it / all idle time.
Nothing where the run has no device trace; 0 where the trace holds no
such span (a program that lacks it: the parent of the PR that brought
it); the run fails where the trace holds no span of the program at all,
as ``host_spans`` fails it.
"""

from __future__ import annotations

import importlib.util
import json
import os

import trace_reduce


def _host_spans():
    """The sibling reader (readers are loaded by path, not as a
    package)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "host_spans.py")
    spec = importlib.util.spec_from_file_location("_reader_host_spans", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fold(spans: list, span: str) -> list:
    """``host_spans.host_spans``' ``(name, start, end, depth, query_id)``
    with every span nested in a ``span`` of its statement renamed to
    it."""
    outer: dict = {}
    for name, s, e, _depth, qid in spans:
        if name == span:
            outer.setdefault(qid, []).append((s, e))
    return [
        (span if any(os_ <= s and e <= oe for os_, oe in outer.get(qid, ()))
         else name, s, e, depth, qid)
        for name, s, e, depth, qid in spans
    ]


def read(ctx, span):
    tr = ctx.trace
    if tr is None or not tr.get("devices") or not tr.get("xplane"):
        return None
    hs = _host_spans()
    timeline = hs.find_timeline(tr["xplane"])
    if timeline is None:
        raise RuntimeError(
            "no timeline.json beside the trace: the window cannot be "
            "placed on the trace's clock")
    with open(timeline) as fh:
        window = json.load(fh)
    # the planes ``trace_scopes`` loaded for this run, where it ran
    devices = getattr(ctx, "_loaded_trace", None) or trace_reduce.load(
        tr["xplane"])
    if not devices["devices"]:
        return None
    spans = hs.host_spans(tr["xplane"])
    if not spans:
        raise RuntimeError(
            "the device trace holds no span of the program (no host "
            "event with a query_id): the idle time cannot be charged")
    idle = hs.idle_intervals(devices, window["lo_ns"], window["hi_ns"])
    return hs.share(hs.charge(idle, fold(spans, span)), "idle_share_in", span)
