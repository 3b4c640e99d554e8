#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the served SQL path still
starts, and answers correctly, on one TPU v5e at TPC-H SF1.

This process is the client and the supervisor. It never imports jax or
trino_tpu: every phase that needs the chip is a child process, one at a
time, each started only after the previous one has exited (a chip
belongs to one process). What the device is comes from the SERVERS
(``GET /v1/info``), never from this process.

  oracle   a child pinned to the CPU (``JAX_PLATFORMS=cpu`` for that
           child only): generates the TPC-H data, loads it into sqlite
           (``trino_tpu.testing.golden.load_tpch_sqlite``) and judges
           every result with ``golden.assert_rows_match`` under the
           tolerance ``tests/test_tpch_queries.py`` uses (abs 0.006).
  phase A  ``python -m trino_tpu.server.coordinator --schema sf1`` (the
           one-chip deployment: embedded runner, owns the chip, no
           ``JAX_PLATFORMS`` in its environment). Q1, Q3, Q6, Q18 cold
           through ``POST /v1/statement`` with the real client, then
           the same four again; the second pass must compile nothing.
  phase B  ``python -m trino_tpu.server.worker --schema sf1`` (owns the
           chip) behind a fleet-mode coordinator that is explicitly a
           host-only role (``JAX_PLATFORMS=cpu``). Q3 and Q18; then the
           worker is restarted and Q3 runs again: the restarted worker
           must read the persistent compile cache (hits > 0,
           compiles <= 1).
  --chips 4  the mesh executor on four chips (Q3, Q18) and what it is
           compared with — the oracle and the same queries on one
           device of that host — and NOTHING else.

One JSON object per phase on stdout; wall times in them are smoke
timings, not benchmark results. Any failed phase -> non-zero exit and no
``"ok": true``. The last line, on success, exactly:

  {"ok": true, "device": {"platform": "tpu", "kind": "<kind>", "count": N}}

No option makes the device check pass on a CPU:
``JAX_PLATFORMS=cpu python chip_smoke.py --sf tiny`` rehearses the
control flow of every phase and exits non-zero at the device check.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import threading
import time

# the supervisor (children and their logs, ports, the Prometheus
# parser) is the benchmark's: one copy, used read-only here
from benchmarks import supervisor
from benchmarks.supervisor import Child, free_port, http_json, load_client

HERE = os.path.dirname(os.path.abspath(__file__))
#: what the driver allows the whole script, compilation included
TIME_LIMIT_S = 1200.0
#: leave this much for teardown and the last lines
RESERVE_S = 45.0
PHASE_A = ("q01", "q03", "q06", "q18")
PHASE_B = ("q03", "q18")
ABS_TOL = 0.006  # tests/test_tpch_queries.py
NOTE = "smoke timings, not benchmark results"
T0 = time.monotonic()


def elapsed() -> float:
    return time.monotonic() - T0


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def log(msg: str) -> None:
    print(f"[smoke {elapsed():7.1f}s] {msg}", file=sys.stderr, flush=True)


def load_queries() -> dict:
    path = os.path.join(
        HERE, "trino_tpu", "connectors", "tpch", "queries.py"
    )
    spec = importlib.util.spec_from_file_location("_smoke_queries", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.QUERIES


def child_env(platform: str | None, data_cache: str) -> dict:
    """The supervisor's child environment (no JAX_PLATFORMS at all
    unless this child is explicitly a host-only role) plus the one
    variable that is the smoke's own: where the oracle child left the
    generated data."""
    env = supervisor.child_env(platform)
    env["TRINO_TPU_DATA_CACHE"] = data_cache
    return env


def metrics(uri: str) -> dict:
    """The compile-related series of /v1/metrics, summed over labels."""
    series = supervisor.prometheus(supervisor.http_text(f"{uri}/v1/metrics"))
    want = {
        "trino_xla_compile_total": "compiles",
        "trino_xla_compile_seconds_total": "compile_s",
        "trino_persistent_cache_hits_total": "persistent_hits",
        "trino_persistent_cache_degraded": "degraded",
    }
    for name in ("trino_xla_compile_total",
                 "trino_persistent_cache_hits_total",
                 "trino_persistent_cache_degraded"):
        if name not in series:
            raise RuntimeError(f"{uri}/v1/metrics carries no {name}")
    return {key: series.get(name, 0.0) for name, key in want.items()}


def delta(after: dict, before: dict) -> dict:
    return {
        "compiles": int(after["compiles"] - before["compiles"]),
        "compile_s": round(after["compile_s"] - before["compile_s"], 3),
        "persistent_hits": int(
            after["persistent_hits"] - before["persistent_hits"]
        ),
    }


def device_check(info: dict, failures: list, who: str, want_count: int = 1):
    """The server must say tpu. Nothing here can be talked into
    passing on a CPU."""
    if info.get("platform") != "tpu":
        failures.append(
            f"device check: {who} reports platform "
            f"{info.get('platform')!r}, not 'tpu'"
        )
    elif info.get("device_count") != want_count:
        failures.append(
            f"device check: {who} reports {info.get('device_count')} "
            f"devices, want {want_count}"
        )


def peak_bytes(info: dict) -> int | None:
    vals = [
        m.get("peak_bytes_in_use") for m in info.get("device_memory", [])
        if m.get("peak_bytes_in_use") is not None
    ]
    return max(vals) if vals else None


def bytes_in_use(info: dict) -> int | None:
    vals = [
        m.get("bytes_in_use") for m in info.get("device_memory", [])
        if m.get("bytes_in_use") is not None
    ]
    return sum(vals) if vals else None


# ---------------------------------------------------------------------------
# the oracle child (CPU-pinned) — this half of the file imports trino_tpu
# ---------------------------------------------------------------------------


def oracle_child(sf: str) -> int:
    """Runs with JAX_PLATFORMS=cpu. Protocol on stdin/stdout, one JSON
    object per line: announces ``{"oracle": "data"}`` once the columns
    are generated, ``{"oracle": "ready"}`` once sqlite holds them and
    the expected rows exist, then answers
    ``{"query", "columns", "rows"}`` with ``{"correct", "detail"}``."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print(json.dumps({"oracle": "refused: not pinned to the CPU"}),
              flush=True)
        return 2
    from decimal import Decimal

    from trino_tpu.connectors.tpch.connector import TpchConnector
    from trino_tpu.connectors.tpch.generator import SCHEMAS
    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.testing.golden import (
        assert_rows_match, load_tpch_sqlite, to_sqlite,
    )

    t0 = time.monotonic()
    data = TpchConnector().data(sf)
    tables = ["customer", "orders", "lineitem"]
    for t in tables:  # generate (and store) every column exactly once
        for col, _ in SCHEMAS[t].columns:
            data.column(t, col)
    gen_s = time.monotonic() - t0
    print(json.dumps({"oracle": "data", "datagen_s": round(gen_s, 1)}),
          flush=True)
    conn = load_tpch_sqlite(data, tables=tables)
    expected = {}
    for q in PHASE_A:
        expected[q] = conn.execute(to_sqlite(QUERIES[q])).fetchall()
    print(json.dumps({
        "oracle": "ready", "datagen_s": round(gen_s, 1),
        "sqlite_s": round(time.monotonic() - t0 - gen_s, 1),
        "lineitem_rows": int(len(data.column("lineitem", "orderkey"))),
    }), flush=True)
    for line in sys.stdin:
        req = json.loads(line)
        q = req["query"]
        types = [c.get("type", "") for c in req["columns"]]
        rows = [
            tuple(
                Decimal(v) if v is not None and t.startswith("decimal")
                else v
                for v, t in zip(row, types)
            )
            for row in req["rows"]
        ]
        ordered = "order by" in QUERIES[q].lower()
        try:
            assert_rows_match(
                rows, expected[q], ordered=ordered, abs_tol=ABS_TOL
            )
            ans = {"correct": True, "expected_rows": len(expected[q])}
        except AssertionError as e:
            ans = {"correct": False, "detail": str(e)[:600]}
        print(json.dumps(ans), flush=True)
    return 0


class Oracle:
    def __init__(self, sf: str, data_cache: str, logdir: str):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--oracle-child",
             "--sf", sf],
            env=child_env("cpu", data_cache), cwd=HERE,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=open(os.path.join(logdir, "oracle.log"), "w"),
            text=True,
        )
        self.info: dict = {}

    def _read(self, timeout_s: float) -> dict:
        box: list = []
        t = threading.Thread(
            target=lambda: box.append(self.proc.stdout.readline()),
            daemon=True,
        )
        t.start()
        t.join(timeout_s)
        if not box or not box[0]:
            raise RuntimeError(
                f"oracle child gave no answer in {timeout_s:.0f}s "
                f"(rc={self.proc.poll()})"
            )
        return json.loads(box[0])

    def wait(self, stage: str, timeout_s: float) -> dict:
        while True:
            msg = self._read(timeout_s)
            if "oracle" not in msg or msg["oracle"].startswith("refused"):
                raise RuntimeError(f"oracle child: {msg}")
            self.info.update(msg)
            if msg["oracle"] == stage:
                return msg

    def judge(self, q: str, columns, rows) -> dict:
        if self.info.get("oracle") != "ready":
            # sqlite loads while the first statement compiles
            self.wait("ready", 900)
        self.proc.stdin.write(json.dumps(
            {"query": q, "columns": columns, "rows": rows}
        ) + "\n")
        self.proc.stdin.flush()
        return self._read(120.0)

    def stop(self):
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def run_statement(client, metrics_uri: str, sql: str):
    """(columns, rows, wall ms, compile-counter deltas of the server at
    ``metrics_uri`` — the one that owns the chip) of one statement."""
    before = metrics(metrics_uri)
    t = time.monotonic()
    columns, rows = client.execute(sql)
    wall_ms = (time.monotonic() - t) * 1e3
    return columns, rows, wall_ms, delta(metrics(metrics_uri), before)


def phase_a(args, client_mod, queries, oracle, data_cache, logdir) -> dict:
    out: dict = {"phase": "A", "server": "embedded coordinator",
                 "sf": args.sf, "seed": args.seed, "note": NOTE,
                 "statements": []}
    failures: list[str] = []
    coord = Child(
        "coordinator-a",
        [sys.executable, "-m", "trino_tpu.server.coordinator",
         "--schema", args.sf, "--port", "0"],
        child_env(None, data_cache), logdir,
    )
    try:
        line = coord.wait_line("coordinator ready on port", 300)
        uri = f"http://127.0.0.1:{int(line.rsplit(' ', 1)[1])}"
        client = client_mod.StatementClient(uri, timeout=TIME_LIMIT_S)
        by_q: dict = {}
        for pass_name in ("cold", "warm"):
            pass_before = metrics(uri)
            for q in PHASE_A:
                columns, rows, ms, d = run_statement(client, uri, queries[q])
                verdict = oracle.judge(q, columns, rows)
                st = by_q.setdefault(q, {"q": q})
                st[f"{pass_name}_ms"] = round(ms, 1)
                st[f"{pass_name}_compiles"] = d["compiles"]
                st[f"{pass_name}_compile_s"] = d["compile_s"]
                st[f"{pass_name}_persistent_hits"] = d["persistent_hits"]
                st["rows"] = len(rows)
                st["correct"] = bool(
                    st.get("correct", True) and verdict["correct"]
                )
                if not verdict["correct"]:
                    failures.append(
                        f"{q} ({pass_name}): {verdict.get('detail')}"
                    )
                log(f"A {q} {pass_name}: {ms:.0f} ms, {len(rows)} rows, "
                    f"compiles {d['compiles']} ({d['compile_s']:.1f}s), "
                    f"correct={verdict['correct']}")
            pd = delta(metrics(uri), pass_before)
            out[f"{pass_name}_pass_compiles"] = pd["compiles"]
            out[f"{pass_name}_pass_compile_s"] = pd["compile_s"]
            out[f"{pass_name}_pass_persistent_hits"] = pd["persistent_hits"]
        out["statements"] = [by_q[q] for q in PHASE_A]
        if out["warm_pass_compiles"] != 0:
            failures.append(
                f"second pass compiled {out['warm_pass_compiles']} programs"
            )
        m = metrics(uri)
        out["degraded"] = int(m["degraded"])
        if m["degraded"] != 0:
            failures.append("trino_persistent_cache_degraded != 0")
        info = http_json(f"{uri}/v1/info")
        out.update(platform=info.get("platform"),
                   device_kind=info.get("device_kind"),
                   device_count=info.get("device_count"),
                   device_bytes_in_use=bytes_in_use(info),
                   peak_device_bytes=peak_bytes(info))
        device_check(info, failures, "the embedded coordinator")
        # the data is on the chip: a server-side fact, with the platform
        if info.get("platform") == "tpu" and not (
            (bytes_in_use(info) or 0) > 0
        ):
            failures.append("no device bytes in use after four queries")
    except Exception as e:  # a phase records its failure and goes on
        failures.append(f"{type(e).__name__}: {e}"[:900])
    finally:
        rc = coord.stop()
        out["server_exit"] = rc
        if rc is None:
            failures.append("embedded coordinator had to be killed")
    out["failures"] = failures
    out["ok"] = not failures
    return out


def phase_b(args, client_mod, queries, oracle, data_cache, logdir,
            budget_end: float) -> dict:
    out: dict = {"phase": "B", "server": "one-worker fleet", "sf": args.sf,
                 "note": NOTE, "statements": [], "cut": []}
    failures: list[str] = []
    spool = os.path.join(logdir, "spool")
    os.makedirs(spool, exist_ok=True)
    wport = free_port()
    wuri = f"http://127.0.0.1:{wport}"

    def start_worker(tag: str) -> Child:
        w = Child(
            f"worker-{tag}",
            [sys.executable, "-m", "trino_tpu.server.worker",
             "--schema", args.sf, "--port", str(wport)],
            child_env(None, data_cache), logdir,
        )
        w.wait_line("worker ready on port", 300)
        return w

    worker = coord = None
    try:
        worker = start_worker("1")
        # the fleet coordinator is a host-only role, explicitly
        coord = Child(
            "coordinator-b",
            [sys.executable, "-m", "trino_tpu.server.coordinator",
             "--schema", args.sf, "--port", "0", "--workers", wuri,
             "--spool", spool, "--n-partitions", "1"],
            child_env("cpu", data_cache), logdir,
        )
        line = coord.wait_line("coordinator ready on port", 300)
        curi = f"http://127.0.0.1:{int(line.rsplit(' ', 1)[1])}"
        client = client_mod.StatementClient(curi, timeout=TIME_LIMIT_S)
        slowest = 0.0
        for q in PHASE_B:
            # cut statements (never the comparison) when the clock says
            # the rest cannot fit: keep room for restart + Q3 again
            if q != PHASE_B[0] and (
                time.monotonic() + 1.5 * slowest + 120 > budget_end
            ):
                out["cut"].append(f"{q}: not enough time left")
                log(f"B {q}: CUT ({budget_end - time.monotonic():.0f}s left)")
                continue
            columns, rows, ms, d = run_statement(client, wuri, queries[q])
            slowest = max(slowest, ms / 1e3)
            verdict = oracle.judge(q, columns, rows)
            out["statements"].append({
                "q": q, "cold_ms": round(ms, 1), "rows": len(rows),
                "correct": verdict["correct"],
                "worker_compiles": d["compiles"],
                "worker_compile_s": d["compile_s"],
                "worker_persistent_hits": d["persistent_hits"],
            })
            if not verdict["correct"]:
                failures.append(f"{q}: {verdict.get('detail')}")
            log(f"B {q}: {ms:.0f} ms, {len(rows)} rows, worker compiles "
                f"{d['compiles']} ({d['compile_s']:.1f}s) hits "
                f"{d['persistent_hits']}, correct={verdict['correct']}")
        # a worker initialises its backend with its first task, so the
        # device is asked for after the statements
        winfo = http_json(f"{wuri}/v1/info")
        device_check(winfo, failures, "the worker")
        out.update(platform=winfo.get("platform"),
                   device_kind=winfo.get("device_kind"),
                   peak_device_bytes=peak_bytes(winfo),
                   device_bytes_in_use=bytes_in_use(winfo))
        wm = metrics(wuri)
        if wm["degraded"] != 0:
            failures.append("worker trino_persistent_cache_degraded != 0")
        # does the fleet coordinator touch a JAX backend at all?
        cinfo = http_json(f"{curi}/v1/info")
        out["fleet_coordinator_backend"] = cinfo.get("platform")
        if cinfo.get("platform") not in (None, "cpu"):
            failures.append(
                "the host-only fleet coordinator initialised "
                f"{cinfo.get('platform')!r}"
            )
        # restart the worker: the new process must find the programs
        # in the persistent compile cache
        if time.monotonic() + 90 > budget_end:
            out["cut"].append("worker restart: not enough time left")
        else:
            rc = worker.stop()
            if rc is None:
                failures.append("worker had to be killed")
            worker = start_worker("2")
            columns, rows, ms, d = run_statement(
                client, wuri, queries["q03"]
            )
            verdict = oracle.judge("q03", columns, rows)
            out["restart"] = {
                "q": "q03", "ms": round(ms, 1), "rows": len(rows),
                "correct": verdict["correct"],
                "worker_compiles": d["compiles"],
                "worker_compile_s": d["compile_s"],
                "worker_persistent_hits": d["persistent_hits"],
            }
            log(f"B restart q03: {ms:.0f} ms, compiles {d['compiles']}, "
                f"hits {d['persistent_hits']}, "
                f"correct={verdict['correct']}")
            if not verdict["correct"]:
                failures.append(f"q03 after restart: {verdict.get('detail')}")
            if d["persistent_hits"] <= 0:
                failures.append("restarted worker: 0 persistent-cache hits")
            if d["compiles"] > 1:
                failures.append(
                    f"restarted worker compiled {d['compiles']} programs"
                )
            if metrics(wuri)["degraded"] != 0:
                failures.append("restarted worker degraded its cache")
    except Exception as e:
        failures.append(f"{type(e).__name__}: {e}"[:900])
    finally:
        for c in (coord, worker):
            if c is not None and c.stop() is None:
                failures.append(f"{c.name} had to be killed")
    out["failures"] = failures
    out["ok"] = not failures
    return out


# ---------------------------------------------------------------------------
# --chips 4: the mesh executor, and what it is compared with
# ---------------------------------------------------------------------------


def mesh_child(sf: str, chips: int) -> int:
    """One process drives all chips of the host: Q3 and Q18 on a mesh of
    ``chips`` devices, then the same two on one device. Prints one JSON
    object (rows are judged by the parent's oracle)."""
    import jax
    import numpy as np

    from trino_tpu.connectors.tpch.queries import QUERIES
    from trino_tpu.engine import QueryRunner
    from trino_tpu.parallel.core import make_mesh

    devs = jax.devices()
    out: dict = {
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "device_count": len(devs), "mesh": {}, "one_device": {},
    }

    def rows_json(res):
        return [[str(v) if not isinstance(v, (int, float, str, type(None)))
                 else v for v in row] for row in res.rows]

    def cols(res):
        return [{"name": n, "type": "decimal" if any(
            type(r[i]).__name__ == "Decimal" for r in res.rows
        ) else ""} for i, n in enumerate(res.names)]

    runner = QueryRunner.tpch(sf, mesh=make_mesh(chips))
    for q in PHASE_B:
        t = time.monotonic()
        res = runner.execute(QUERIES[q])
        out["mesh"][q] = {
            "ms": round((time.monotonic() - t) * 1e3, 1),
            "columns": cols(res), "rows": rows_json(res),
        }
    # every device holds a shard of a scanned column
    cache = runner.executor._dist_scan_cache
    key = next(k for k in cache if k[2] == "lineitem")
    mask = cache[key][""]
    out["lineitem_shards"] = [
        {"device": s.device.id, "rows": int(np.prod(s.data.shape))}
        for s in mask.addressable_shards
    ]
    out["bytes_in_use"] = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in devs[:chips]
    ]
    del runner
    single = QueryRunner.tpch(sf)
    for q in PHASE_B:
        t = time.monotonic()
        res = single.execute(QUERIES[q])
        out["one_device"][q] = {
            "ms": round((time.monotonic() - t) * 1e3, 1),
            "columns": cols(res), "rows": rows_json(res),
        }
    print("MESH-RESULT " + json.dumps(out), flush=True)
    return 0


def phase_mesh(args, oracle, data_cache, logdir) -> dict:
    out: dict = {"phase": "mesh", "chips": args.chips, "sf": args.sf,
                 "note": NOTE}
    failures: list[str] = []
    child = Child(
        "mesh",
        [sys.executable, os.path.abspath(__file__), "--mesh-child",
         "--sf", args.sf, "--chips", str(args.chips)],
        child_env(None, data_cache), logdir,
    )
    try:
        line = child.wait_line("MESH-RESULT ", TIME_LIMIT_S * 2.5)
        res = json.loads(line[len("MESH-RESULT "):])
        out.update(platform=res["platform"], device_kind=res["device_kind"],
                   device_count=res["device_count"],
                   lineitem_shards=res["lineitem_shards"],
                   bytes_in_use=res["bytes_in_use"], statements=[])
        device_check(res, failures, "the mesh child", args.chips)
        held = {s["device"] for s in res["lineitem_shards"] if s["rows"] > 0}
        if len(held) != args.chips:
            failures.append(
                f"lineitem is sharded over {len(held)} devices, "
                f"not {args.chips}"
            )
        if res["platform"] == "tpu" and not all(
            (b or 0) > 0 for b in res["bytes_in_use"]
        ):
            failures.append(f"idle device memory: {res['bytes_in_use']}")
        for q in PHASE_B:
            st = {"q": q}
            for side in ("mesh", "one_device"):
                r = res[side][q]
                v = oracle.judge(q, r["columns"], r["rows"])
                st[f"{side}_ms"] = r["ms"]
                st[f"{side}_correct"] = v["correct"]
                st["rows"] = len(r["rows"])
                if not v["correct"]:
                    failures.append(f"{q} {side}: {v.get('detail')}")
            st["agree"] = res["mesh"][q]["rows"] == res["one_device"][q]["rows"]
            out["statements"].append(st)
    except Exception as e:
        failures.append(f"{type(e).__name__}: {e}"[:900])
    finally:
        child.stop()
    out["failures"] = failures
    out["ok"] = not failures
    return out


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Served-path smoke on one TPU v5e at TPC-H SF1.",
    )
    ap.add_argument(
        "--seed", type=int, default=0,
        help="recorded on the phase lines. TPC-H's generator is a fixed "
             "function of the scale factor (dbgen semantics: every "
             "column stream is seeded from SF, table and column), so the "
             "seed does NOT vary the dataset; nothing else here is "
             "random",
    )
    ap.add_argument(
        "--sf", default="sf1", choices=("sf1", "tiny"),
        help="'tiny' is for rehearsing the control flow on a CPU, where "
             "the device check fails and the exit code is non-zero",
    )
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: run ONLY the mesh-executor phase on four chips and "
             "its one-device comparison",
    )
    ap.add_argument("--oracle-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--mesh-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.oracle_child:
        return oracle_child(args.sf)
    if args.mesh_child:
        return mesh_child(args.sf, args.chips)

    # everything the run writes lives under one git-ignored directory of
    # the checkout; the data cache starts EMPTY (a stale cache from an
    # older generator could make engine and oracle agree on wrong data)
    work = os.path.join(HERE, ".smoke", f"run-{os.getpid()}")
    data_cache = os.path.join(work, "tpch")
    logdir = os.path.join(work, "logs")
    os.makedirs(data_cache)
    os.makedirs(logdir)
    ok = True
    device = None
    oracle = None
    try:
        client_mod = load_client()
        queries = load_queries()
        # the oracle goes first and pays data generation ONCE: the
        # servers find every column in the data cache; while it loads
        # sqlite (no chip needed) phase A already compiles
        oracle = Oracle(args.sf, data_cache, logdir)
        gen = oracle.wait("data", 900)
        log(f"data generated in {gen['datagen_s']}s")
        if args.chips == 4:
            res = phase_mesh(args, oracle, data_cache, logdir)
            res["setup"] = dict(oracle.info)
            emit(res)
            ok = res["ok"]
            if ok:
                device = {"platform": res["platform"],
                          "kind": res["device_kind"],
                          "count": res["device_count"]}
        else:
            a = phase_a(args, client_mod, queries, oracle, data_cache, logdir)
            a["setup"] = dict(oracle.info)
            a["elapsed_s"] = round(elapsed(), 1)
            emit(a)
            b = phase_b(
                args, client_mod, queries, oracle, data_cache, logdir,
                budget_end=T0 + TIME_LIMIT_S - RESERVE_S,
            )
            b["elapsed_s"] = round(elapsed(), 1)
            emit(b)
            ok = a["ok"] and b["ok"]
            if ok:
                device = {"platform": a["platform"],
                          "kind": a["device_kind"],
                          "count": a["device_count"]}
    except Exception as e:
        ok = False
        emit({"phase": "supervisor", "ok": False,
              "failures": [f"{type(e).__name__}: {e}"[:900]]})
    finally:
        if oracle is not None:
            oracle.stop()
        # logs of a failed run stay for the post-mortem; the data (GBs
        # at SF1) never does
        shutil.rmtree(data_cache, ignore_errors=True)
        if ok:
            shutil.rmtree(work, ignore_errors=True)
    if not ok or device is None:
        log("FAILED")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
