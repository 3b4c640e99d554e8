"""What a raw ``.xplane.pb`` says of each device operation beyond its
name and time: the metadata of the device planes' events.

``jax.profiler.ProfileData`` (``trace_reduce.load``) hands out an
event's name, start, duration and its *own* stats. The file holds more:
every event points at an ``XEventMetadata`` of its plane, and on a TPU
the metadata of an ``XLA Ops`` event carries, as stats of its own,

  ``tf_op``           the instruction's whole jax ``op_name`` path and
                      a ``:<op type>`` tail, e.g.
                      ``jit(join_count)/op:Join/k:join_ranges/gather:``
                      — the program's ``jax.named_scope``s are in it
  ``program_id``      the number in brackets in the module's name,
                      ``jit_join_count(3989226972488341136)``
  ``hlo_category``    XLA's class of the instruction
  ``bytes_accessed``  bytes the instruction reads and writes, by XLA's
                      cost model
  ``source``          ``<file>:<line>`` of the Python that traced it

This module decodes those maps by the protobuf wire format with the
standard library alone, and skips every plane's ``lines`` (the events)
by their length: the cost is the metadata's size, not the trace's.
Events stay ``ProfileData``'s. An event is joined to its metadata by
(the ``program_id`` of the ``XLA Modules`` event around it, its name):
an event's name is the instruction's whole text, and two programs may
hold the same text.

Fields read (tensorflow/tsl ``xplane.proto``): XSpace{1: planes};
XPlane{2: name, 3: lines, 4: event_metadata, 5: stat_metadata} (maps:
entries of {1: key, 2: value}); XEventMetadata{1: id, 2: name, 5:
stats}; XStatMetadata{1: id, 2: name}; XStat{1: metadata_id, 2:
double, 3: uint64, 4: int64, 5: str, 6: bytes, 7: ref}.
"""

from __future__ import annotations

import re

DEVICE_PREFIX = "/device:TPU:"
#: the stats of an event's metadata that are kept
KEPT = ("tf_op", "program_id", "hlo_category", "bytes_accessed", "source")

_PROGRAM_ID = re.compile(r"\((\d+)\)\s*$")


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, lo: int, hi: int):
    """``(field number, value)`` of the message in ``buf[lo:hi]``: a
    varint as its number, a length-delimited field as its ``(start,
    end)`` in ``buf`` (nothing is copied, so a field that is not wanted
    costs its key and its length), a fixed field as its bytes."""
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
            raise ValueError(f"wire type {wire}: not an .xplane.pb")
        yield key >> 3, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    for f, v in fields(buf, *span):
        if f == 2:
            return v
    return None


def program_id_of(module_name: str) -> int | None:
    """``jit_compact(5500588580862621041)`` -> 5500588580862621041."""
    m = _PROGRAM_ID.search(module_name)
    return int(m.group(1)) if m else None


def device_metadata(path: str) -> dict:
    """``{plane name: {(program_id, event name): {"tf_op", "program_id",
    "hlo_category", "bytes_accessed", "source"}}}`` for every device
    plane of the raw trace at ``path``; a stat the file does not hold is
    None. Where two metadata of one program share a name (they do not,
    in the traces seen), the first is kept."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    out: dict = {}
    for f, span in fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, stat_spans, event_spans = "", [], []
        for f2, v in fields(buf, *span):
            if f2 == 2:
                name = _text(buf, v)
            elif f2 == 4:
                event_spans.append(v)
            elif f2 == 5:
                stat_spans.append(v)
        if not name.startswith(DEVICE_PREFIX):
            continue
        stat_names = {}
        for v in stat_spans:
            val = _map_value(buf, v)
            if val is not None:
                sm = dict(fields(buf, *val))
                if sm.get(2) is not None:
                    stat_names[sm.get(1)] = _text(buf, sm[2])
        wanted = {i: n for i, n in stat_names.items() if n in KEPT}
        plane: dict = {}
        for v in event_spans:
            val = _map_value(buf, v)
            if val is None:
                continue
            md = dict.fromkeys(KEPT)
            ev_name = ""
            for f3, v3 in fields(buf, *val):
                if f3 == 2:
                    ev_name = _text(buf, v3)
                elif f3 == 5:
                    stat = dict(fields(buf, *v3))
                    key = wanted.get(stat.get(1))
                    if key is None:
                        continue
                    if 5 in stat:
                        md[key] = _text(buf, stat[5])
                    else:
                        md[key] = stat.get(3, stat.get(4))
            plane.setdefault((md["program_id"], ev_name), md)
        out[name] = plane
    return out
