"""The window's statements on the client's clock.

args: ``quantity``
  "sender_lag_ms"    mean of sent minus due (open loop): how late the
                     generator ran
  "latency_ms"       mean latency as the end-to-end metrics count it
  "geomean_ms"       query_geomean_ms's arithmetic (e2e.py) over the window
  "late_stmt_share"  100 * statements whose latency is over LATE_FACTOR
                     times their template's median in this window / all
                     statements: what a median sets aside
``cls``: only statements of that class."""

import statistics

#: how far over its template's median a statement counts as late
LATE_FACTOR = 1.5


def read(ctx, quantity, cls=None):
    if quantity in ("geomean_ms", "late_stmt_share"):
        lat: dict = {}
        for st in ctx.statements:
            if cls is None or st.cls == cls:
                lat.setdefault(st.template, []).append(ctx.latency_ms(st))
        if quantity == "geomean_ms":
            import e2e

            return e2e.query_geomean_ms(lat)
        total = sum(len(v) for v in lat.values())
        late = 0
        for v in lat.values():
            limit = LATE_FACTOR * statistics.median(v)
            late += sum(x > limit for x in v)
        return 100.0 * late / total if total else None
    vals = []
    for st in ctx.statements:
        if cls is not None and st.cls != cls:
            continue
        if st.error is not None or st.sent_s is None:
            if quantity == "latency_ms":
                vals.append(ctx.latency_ms(st))
            continue
        if quantity == "sender_lag_ms":
            vals.append((st.sent_s - ctx.t0 - st.due_s) * 1e3)
        elif quantity == "latency_ms":
            vals.append(ctx.latency_ms(st))
        else:
            raise ValueError(f"unknown quantity {quantity!r}")
    return sum(vals) / len(vals) if vals else None
