"""The mesh exchange's share of the interconnect's peak.

    100 * (live payload bytes a chip sent / ICI bytes per second)
        / (seconds a device spent in the exchange programs)

Bytes: ``mesh_exchange_live_bytes`` of ``GET /v1/query`` summed over the
window's statements (the rows that had to move at the width of a row,
``trino_tpu/exec/mesh.py``), divided by the cell's chips: rows are
spread evenly, so that is what one chip sent. Not the padded buffers
the all_to_all is handed: a kernel with other padding is then read
against the same work. Peak: ``peaks.json``'s ``ici_gbits_per_s`` / 8.
Time: the trace's executions of the programs whose name starts
``jit_mesh_exchange`` (the all_to_all and the program that hashes rows
to their shard), clipped to the window and summed over the device
planes, divided by the number of planes: the mean a device.

Nothing where the run has no device trace, no statement row carries
the field (a server without a mesh executor's spans), or the trace
holds no such program.
"""

from __future__ import annotations

import importlib.util
import json
import os

import trace_reduce

PROGRAM_PREFIX = "jit_mesh_exchange"


def share(live_bytes: float, chips: int, ici_gbits_per_s: float,
          program_s: list) -> float | None:
    """``program_s``: seconds in the exchange programs, one a device."""
    if not program_s or chips < 1:
        return None
    mean_s = sum(program_s) / len(program_s)
    if mean_s <= 0 or live_bytes <= 0:
        return None
    peak = ici_gbits_per_s * 1e9 / 8.0
    return 100.0 * (live_bytes / chips / peak) / mean_s


def program_seconds(devices: dict, lo_ns: float, hi_ns: float) -> list:
    """Seconds inside [lo, hi] in the exchange programs, one number a
    device plane of ``trace_reduce.load(...)["devices"]``."""
    out = []
    for dev in devices.values():
        total = 0.0
        for name, s, e in dev["modules"]:
            if name.strip().startswith(PROGRAM_PREFIX) and e > lo_ns and s < hi_ns:
                total += (min(e, hi_ns) - max(s, lo_ns)) / 1e9
        out.append(total)
    return out


def window_live_bytes(ctx) -> float | None:
    by_id = {q.get("query_id"): q for q in ctx.query_list}
    vals = [by_id[st.query_id].get("mesh_exchange_live_bytes")
            for st in ctx.statements if st.query_id in by_id]
    vals = [float(v) for v in vals if v is not None]
    return sum(vals) if vals else None


def _host_spans():
    """The sibling reader, for how it finds the window on the trace's
    clock (readers are loaded by path, not as a package)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "host_spans.py")
    spec = importlib.util.spec_from_file_location("_reader_host_spans", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.get("devices") or not tr.get("xplane"):
        return None
    live = window_live_bytes(ctx)
    if live is None:
        return None
    timeline = _host_spans().find_timeline(tr["xplane"])
    if timeline is None:
        return None
    with open(timeline) as fh:
        window = json.load(fh)
    devices = trace_reduce.load(tr["xplane"])["devices"]
    peaks = ctx.peaks[ctx.info["device_kind"]]
    return share(live, int(ctx.cell["chips"]), peaks["ici_gbits_per_s"],
                 program_seconds(devices, window["lo_ns"], window["hi_ns"]))
