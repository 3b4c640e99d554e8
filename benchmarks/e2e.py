"""The arithmetic of the end-to-end metrics, on plain lists.

Every statement of the window counts in ``queries_per_s``: over the
whole window, first send to last reply, so a stall or a tail costs it
one for one. ``query_geomean_ms`` is what a statement of each template
*typically* costs its caller, the short templates weighed like the
long: a template's median over all its executions of the window. What
the median sets aside (one statement in thirty a few times late, a
freeze of the machine) is held by ``queries_per_s``, by the limit 0 on
failed statements, and shown by the per-layer ``protocol.late_stmt_share``
(PERF.md, section 2). A failed statement's latency counts as beyond any
limit.
"""

from __future__ import annotations

import math
import statistics

#: what a failed or refused statement's latency counts as, in ms
BEYOND_ANY_LIMIT_MS = 3_600_000.0


def latency_ms(st, from_due: bool, t0: float) -> float:
    """Client latency of one statement: from send (closed loop) or from
    the instant it was due (open loop) to the last row."""
    if st.error is not None or st.done_s is None:
        return BEYOND_ANY_LIMIT_MS
    start = t0 + st.due_s if from_due else st.sent_s
    return (st.done_s - start) * 1e3


def query_geomean_ms(latencies_by_template: dict) -> float | None:
    """TPC-H power style: the geometric mean, over the templates, of
    each template's median latency over ALL its executions."""
    typical = [statistics.median(v) for v in latencies_by_template.values()
               if v]
    if not typical:
        return None
    return math.exp(sum(math.log(m) for m in typical) / len(typical))


def queries_per_s(n_correct: int, first_send_s: float,
                  last_reply_s: float) -> float | None:
    span = last_reply_s - first_send_s
    return n_correct / span if span > 0 else None
