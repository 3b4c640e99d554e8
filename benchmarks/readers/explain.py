"""Mean client latency of ``EXPLAIN <statement>`` over the mix's
templates, sent after the window has closed: parse, analyse and plan,
timed from outside until the program has spans for them.

args: ``cls`` (only templates of that class), ``repeats``."""

import time


def read(ctx, cls=None, repeats=3):
    import traffic

    vals = []
    seen = set()
    for st in traffic.all_statements(ctx.mix):
        if (cls is not None and st.cls != cls) or st.template in seen:
            continue
        seen.add(st.template)
        cl = ctx.client()
        for _ in range(repeats):
            t = time.monotonic()
            cl.execute("explain " + st.sql)
            vals.append((time.monotonic() - t) * 1e3)
    return sum(vals) / len(vals) if vals else None
