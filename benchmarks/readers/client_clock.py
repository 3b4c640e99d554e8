"""Means over the window's statements on the client's clock.

args: ``quantity``
  "overhead_ms"    client latency minus the server's elapsedTimeMillis
  "sender_lag_ms"  sent minus due (open loop): how late the generator ran
  "latency_ms"     latency as the end-to-end metrics count it
  "geomean_ms"     query_geomean_ms's arithmetic (e2e.py) over the window
``cls``: only statements of that class."""


def read(ctx, quantity, cls=None):
    if quantity == "geomean_ms":
        import e2e

        lat: dict = {}
        for st in ctx.statements:
            if cls is None or st.cls == cls:
                lat.setdefault(st.template, []).append(ctx.latency_ms(st))
        return e2e.query_geomean_ms(lat)
    vals = []
    for st in ctx.statements:
        if cls is not None and st.cls != cls:
            continue
        if st.error is not None or st.sent_s is None:
            if quantity == "latency_ms":
                vals.append(ctx.latency_ms(st))
            continue
        if quantity == "overhead_ms":
            if st.server_ms is not None:
                vals.append((st.done_s - st.sent_s) * 1e3 - st.server_ms)
        elif quantity == "sender_lag_ms":
            vals.append((st.sent_s - ctx.t0 - st.due_s) * 1e3)
        elif quantity == "latency_ms":
            vals.append(ctx.latency_ms(st))
        else:
            raise ValueError(f"unknown quantity {quantity!r}")
    return sum(vals) / len(vals) if vals else None
