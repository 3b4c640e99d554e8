"""Drives a window through the real client, on the client's clock."""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import traffic


def timed_client(client_mod, uri: str, timeout: float):
    """The real StatementClient, keeping the last protocol response so
    that the query id and the server's own elapsed time can be read."""

    class TimedClient(client_mod.StatementClient):
        last: dict = {}

        def _request(self, method, url, body=None):
            resp = super()._request(method, url, body)
            if isinstance(resp, dict) and "stats" in resp:
                self.last = resp
            return resp

    return TimedClient(uri, timeout=timeout)


def run_one(client_mod, uri: str, st, timeout: float) -> None:
    """POST /v1/statement and its nextUri pages, to the last row."""
    cl = timed_client(client_mod, uri, timeout)
    st.sent_s = time.monotonic()
    try:
        st.columns, st.rows = cl.execute(st.sql)
    except Exception as e:  # the statement failed; the window goes on
        st.error = f"{type(e).__name__}: {e}"[:300]
    st.done_s = time.monotonic()
    st.query_id = cl.last.get("id")
    st.server_ms = (cl.last.get("stats") or {}).get("elapsedTimeMillis")


def closed_window(client_mod, uri: str, mix: dict, seed: int,
                  seconds: float, timeout: float):
    """Whole passes only: passes start while the clock is under
    ``seconds`` and the pass in flight finishes. Returns (statements,
    t0)."""
    loops = [traffic.ClosedLoop(mix, seed + 7919 * i, i)
             for i in range(int(mix.get("clients", 1)))]
    done: list = []
    lock = threading.Lock()
    t0 = time.monotonic()

    def caller(loop):
        while time.monotonic() - t0 < seconds:
            for st in loop.next_pass():
                run_one(client_mod, uri, st, timeout)
                with lock:
                    done.append(st)

    threads = [threading.Thread(target=caller, args=(lp,)) for lp in loops]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return done, t0


def open_window(client_mod, uri: str, mix: dict, seed: int,
                seconds: float, timeout: float):
    """A fixed multiset of statements, each sent when it is due whatever
    the server does; the window ends at the last reply."""
    sts = traffic.open_schedule(mix, seed, seconds)
    pool = ThreadPoolExecutor(max_workers=int(mix.get("max_in_flight", 64)))
    futures = []
    t0 = time.monotonic()
    for st in sts:
        wait = t0 + st.due_s - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        futures.append(pool.submit(run_one, client_mod, uri, st, timeout))
    for f in futures:
        f.result()
    pool.shutdown(wait=True)
    return sts, t0


def window(client_mod, uri: str, mix: dict, seed: int, seconds: float,
           timeout: float = 300.0):
    if mix["loop"] == "closed":
        return closed_window(client_mod, uri, mix, seed, seconds, timeout)
    if mix["loop"] == "open":
        return open_window(client_mod, uri, mix, seed, seconds, timeout)
    raise ValueError(f"unknown loop kind {mix['loop']!r}")
