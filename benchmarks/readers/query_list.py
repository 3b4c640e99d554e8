"""A per-statement field of ``GET /v1/query`` (the server's clock;
differences only), averaged over the window's statements, matched by
the query id the protocol gave the client. The server's list keeps the
last 200 finished statements, so a traced run reads it every few
seconds through the window (run.py) and ``ctx.query_list`` holds every
entry seen. Where fewer than ``min_share`` of the window's statements
are found all the same, the mean would be of a subset, and the run
fails instead of reporting it.

args: ``field``, ``cls`` (only statements of that class), ``min_share``."""


def read(ctx, field, cls=None, min_share=0.9):
    by_id = {q.get("query_id"): q for q in ctx.query_list}
    wanted = [st for st in ctx.statements if cls is None or st.cls == cls]
    vals = []
    for st in wanted:
        q = by_id.get(st.query_id)
        if q is not None and q.get(field) is not None:
            vals.append(float(q[field]))
    if not wanted:
        return None
    if len(vals) < min_share * len(wanted):
        raise RuntimeError(
            f"/v1/query holds {field} for {len(vals)} of the window's "
            f"{len(wanted)} statements, under {min_share:.0%}")
    return sum(vals) / len(vals)
