"""The one general traffic generator.

A mix is a data file, ``traffic/<mix>.json``; a statement template is
``templates/<name>.sql.txt`` with ``<name>.params.json`` (its closed set
of parameter tuples, its class, what it scans, how its rows compare)
and ``<name>.ref.sql.txt`` (the reference's statement). The seed never
changes what a window is made of: it orders and it picks among the
parameter tuples of the closed set, so that seeds add no compiles.

Two loop kinds:

``closed``  ``clients`` callers, each sending its next statement when
            the last has answered. A *pass* is the templates of
            ``pass`` once each in an order the seed permutes, or, where
            the mix gives ``streams`` (one fixed order a caller, as
            TPC-H's throughput test does), the caller's own stream in
            that order. Passes start while the clock is under
            ``--seconds``; the pass in flight finishes. No window is
            cut mid-pass.
``open``    arrivals at ``rate_per_s`` whatever the server does. The
            window is a fixed multiset: a whole number of blocks of
            ``block`` statements (so many of each class, the templates
            of a class taking turns). The seed shuffles their order,
            draws the gaps between arrivals from the exponential
            distribution (rescaled so that their mean is exactly
            1/rate) and picks the parameter tuples. Every statement is
            due inside ``--seconds``. (No cell is open-loop today:
            PERF.md, Open questions.)
"""

from __future__ import annotations

import json
import math
import os
import random
from dataclasses import dataclass

import reference

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Template:
    name: str
    text: str
    ref_text: str
    cls: str
    tuples: list[dict]
    scans: dict
    compare: dict


@dataclass
class Statement:
    template: str
    cls: str
    params: dict
    sql: str
    #: seconds after the window's start at which it is due (open loop)
    due_s: float = 0.0
    #: pass (closed loop) or block (open loop) it belongs to
    group: int = 0
    # filled in by the load generator
    sent_s: float | None = None
    done_s: float | None = None
    error: str | None = None
    rows: list | None = None
    columns: list | None = None
    server_ms: float | None = None
    query_id: str | None = None
    #: set by the comparison once the window has closed
    correct: bool | None = None

    @property
    def key(self) -> str:
        return json.dumps([self.template, self.params], sort_keys=True)


def load_template(name: str, root: str = HERE) -> Template:
    base = os.path.join(root, "templates", name)
    with open(base + ".params.json") as fh:
        p = json.load(fh)
    with open(base + ".sql.txt") as fh:
        text = fh.read().strip()
    with open(base + ".ref.sql.txt") as fh:
        ref_text = fh.read().strip()
    return Template(name, text, ref_text, p["class"], p["tuples"],
                    p.get("scans", {}), p["reference"])


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", name + ".json")) as fh:
        mix = json.load(fh)
    names = list(mix.get("pass", []))
    for stream in mix.get("streams", []):
        names += stream
    for cls_names in mix.get("classes", {}).values():
        names += cls_names
    mix["templates"] = {n: load_template(n, root) for n in dict.fromkeys(names)}
    return mix


def make(t: Template, params: dict, **kw) -> Statement:
    return Statement(t.name, t.cls, params, reference.render(t.text, params),
                     **kw)


def all_statements(mix: dict) -> list[Statement]:
    """Every statement text the mix can send: what set-up warms and the
    reference answers."""
    return [make(t, p) for t in mix["templates"].values() for p in t.tuples]


class ClosedLoop:
    """Passes of one caller of a closed-loop mix, drawn lazily: the
    number of passes is the clock's, their content the seed's."""

    def __init__(self, mix: dict, seed: int, caller: int = 0):
        self.mix = mix
        self.rng = random.Random(seed)
        streams = mix.get("streams")
        #: the caller's own fixed order, where the mix gives streams
        self.stream = streams[caller % len(streams)] if streams else None
        self.n = 0

    def next_pass(self) -> list[Statement]:
        if self.stream is not None:
            names = list(self.stream)
        else:
            names = list(self.mix["pass"])
            self.rng.shuffle(names)
        out = []
        for name in names:
            t = self.mix["templates"][name]
            out.append(make(t, self.rng.choice(t.tuples), group=self.n))
        self.n += 1
        return out


def open_schedule(mix: dict, seed: int, seconds: float) -> list[Statement]:
    """The fixed multiset of an open-loop window, in the seed's order
    at the seed's arrival times, with the seed's parameter tuples."""
    rng = random.Random(seed)
    rate = float(mix["rate_per_s"])
    block = mix["block"]
    size = sum(block.values())
    # blocks after which every class has given each template equally often
    whole = math.lcm(*(
        len(mix["classes"][c]) // math.gcd(len(mix["classes"][c]), n)
        for c, n in block.items()))
    blocks = int(seconds * rate / size) // whole * whole
    if blocks < 1:
        raise ValueError(
            f"{seconds}s at {rate}/s holds no whole rotation of {size * whole}"
        )
    names = []
    for cls, count in block.items():
        of_cls = mix["classes"][cls]
        names += [of_cls[i % len(of_cls)] for i in range(count * blocks)]
    rng.shuffle(names)
    gaps = [rng.expovariate(rate) for _ in names]
    scale = len(names) / (rate * sum(gaps))
    out: list[Statement] = []
    t = 0.0
    for i, (name, gap) in enumerate(zip(names, gaps)):
        tpl = mix["templates"][name]
        out.append(make(tpl, rng.choice(tpl.tuples), group=i // size, due_s=t))
        t += gap * scale
    return out
