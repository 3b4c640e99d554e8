#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is a client and a supervisor; it never imports jax or
trino_tpu. It starts the cell's servers as children with the argv a
deployment uses, warms every statement text of the cell, measures one
window through ``POST /v1/statement`` with the real client, compares
every answer of the window with the plain reference, prints one JSON
line, stops the children and exits.

Nothing about a cell, a configuration, a traffic mix, a template or a
per-layer metric is written in this file: each is a file of its own
that is found by the name BENCHMARK.json gives (see README.md).
"""

from __future__ import annotations

import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import e2e  # noqa: E402
import loadgen  # noqa: E402
import reference  # noqa: E402
import supervisor  # noqa: E402
import traffic  # noqa: E402

#: everything a run writes that is not a cache: logs, spool, raw trace.
#: A fixed path inside the checkout, emptied at the start of each run.
WORK = os.path.join(ROOT, ".bench_work")
#: the reference's database and answers, beside the program's own
#: column cache (both ignored by git and by the chip tool's copy)
REF_CACHE = os.path.join(ROOT, ".tpch_cache", "bench_ref")

#: the limits of the comparison that decides ``correct`` (PERF.md,
#: section 2, gives the readings each was set from)
LIMITS = {
    "statements_failed": 0,
    "statements_wrong": 0,
    "decimal_gap_ulp": 0.0,
    "avg_gap_ulp": 0.5,
    "result_cache_hits": 0,
}


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_PROC:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


class Context:
    """What a per-layer reader may read. Readers take from here; they
    start nothing (one that has to send a statement after the window
    makes its client as the window's did: ``loadgen.timed_client`` over
    ``client_mod`` and ``servers.entry_uri``)."""

    def __init__(self):
        self.cell: dict = {}
        self.config: dict = {}
        self.mix: dict = {}
        self.statements: list = []
        self.t0 = 0.0          # monotonic clock at the window's start
        self.t0_wall_ns = 0    # wall clock at the window's start
        self.from_due = False
        self.servers = None
        self.before: dict = {}  # role -> prometheus series before
        self.after: dict = {}
        self.info: dict = {}    # /v1/info of the chip's owner, after
        self.query_list: list = []
        self.trace: dict | None = None   # trace_reduce.reduce(...)
        self.peaks: dict = {}
        self.client_mod = None

    def latency_ms(self, st) -> float:
        return e2e.latency_ms(st, self.from_due, self.t0)


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def find_cell(bench: dict, name: str):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            for cfg in bench["configs"]:
                if cfg["name"] == cell["config"]:
                    return cell, cfg
            raise SystemExit(f"cell {name} names no known configuration")
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def reported_in(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


class Reference:
    """Expected rows for every statement text of the cell, from sqlite
    over the same generated tables. Answers are kept on disk keyed by
    the statement, its reference text and the tables' stated checksums.
    The CPU-pinned child runs in every run: it checks the cached
    columns against the rows and checksums the configuration states,
    and answers what has no answer on disk yet."""

    def __init__(self, config: dict, schema: str, mix: dict,
                 control: bool = False):
        self.dir = os.path.join(
            REF_CACHE, schema + ("-control" if control else ""))
        self.schema = schema
        self.config = config
        self.stated = config["tables"] if schema == config["schema"] else {}
        data_id = json.dumps([self.stated, config["reference_tables"],
                              control], sort_keys=True)
        self.by_key: dict = {}
        self.request = []
        for st in traffic.all_statements(mix):
            tpl = mix["templates"][st.template]
            ref_sql = reference.render(tpl.ref_text, st.params)
            key = reference.statement_key(schema, st.template, st.params,
                                          ref_sql, data_id)
            self.by_key[st.key] = key
            self.request.append({"key": key, "ref_sql": ref_sql})
        self.control = control
        self.child: subprocess.Popen | None = None
        self.lines: list = []
        self._reader: threading.Thread | None = None

    def missing(self) -> bool:
        return not all(
            os.path.exists(os.path.join(self.dir, k + ".json"))
            for k in self.by_key.values())

    def start(self, logdir: str) -> None:
        req = {
            "schema": self.schema, "dir": self.dir,
            "tables": self.config["reference_tables"],
            "indexes": self.config.get("reference_indexes", {}),
            "stated": self.stated, "statements": self.request,
            "control": self.control,
        }
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "datagen.py")],
            env=supervisor.child_env("cpu"), cwd=ROOT,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=open(os.path.join(logdir, "reference.log"), "w"),
            text=True,
        )
        self.child.stdin.write(json.dumps(req))
        self.child.stdin.close()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.child.stdout:
            try:
                self.lines.append(json.loads(line))
            except ValueError:
                pass

    def wait(self, stage: str, timeout_s: float) -> None:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if any(m.get("ref") == stage for m in self.lines):
                return
            if self.child.poll() is not None:
                self._reader.join(timeout=5)
                if any(m.get("ref") == stage for m in self.lines):
                    return
                raise RuntimeError(
                    f"the reference's child exited rc={self.child.returncode} "
                    f"before '{stage}' (see reference.log)")
            time.sleep(0.1)
        raise TimeoutError(f"the reference's child: no '{stage}'")

    def stop(self) -> None:
        if self.child is not None and self.child.poll() is None:
            self.child.kill()
        if self.child is not None:
            self.child.wait(timeout=30)

    def expected(self, st) -> list:
        return load_json(os.path.join(self.dir, self.by_key[st.key] + ".json"))


def compare(mix: dict, ref: Reference, statements: list) -> dict:
    """Every answer of the window against the reference's: the numbers
    compared, each beside its limit, and which statements were right."""
    nums = {"statements_failed": 0, "statements_wrong": 0,
            "decimal_gap_ulp": 0.0, "avg_gap_ulp": 0.0}
    details = []
    expected_cache: dict = {}
    for st in statements:
        st.correct = False
        if st.error is not None or st.rows is None:
            nums["statements_failed"] += 1
            details.append(f"{st.template}: {st.error}")
            continue
        if st.key not in expected_cache:
            expected_cache[st.key] = ref.expected(st)
        spec = mix["templates"][st.template].compare
        r = reference.compare_statement(
            spec["columns"], spec["ordered"], st.rows, expected_cache[st.key])
        wrong = (r["exact_mismatches"] > 0
                 or r["decimal_gap_ulp"] > LIMITS["decimal_gap_ulp"]
                 or r["avg_gap_ulp"] > LIMITS["avg_gap_ulp"])
        nums["statements_wrong"] += int(wrong)
        nums["decimal_gap_ulp"] = max(nums["decimal_gap_ulp"],
                                      r["decimal_gap_ulp"])
        nums["avg_gap_ulp"] = max(nums["avg_gap_ulp"], r["avg_gap_ulp"])
        st.correct = not wrong
        if wrong and len(details) < 5:
            details.append(f"{st.template} {st.params}: {r['detail']}")
    return {"numbers": nums, "details": details}


# ---------------------------------------------------------------------------
# per-layer metrics: a JSON file each, read by a reader found by name
# ---------------------------------------------------------------------------


def load_reader(name: str):
    path = os.path.join(HERE, "readers", name + ".py")
    spec = importlib.util.spec_from_file_location(f"_reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(bench: dict, cell_name: str, ctx: Context) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if not reported_in(m, cell_name):
            continue
        spec = load_json(os.path.join(HERE, "metrics", m["name"] + ".json"))
        value = load_reader(spec["reader"])(ctx, **spec.get("args", {}))
        if value is None:
            continue  # a reader that finds nothing returns nothing
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(bench: dict, cell_name: str, ctx: Context,
               setup_s: float) -> dict:
    sts = ctx.statements
    lat: dict = {}
    for st in sts:
        lat.setdefault(st.template, []).append(ctx.latency_ms(st))
    first = min(st.sent_s for st in sts)
    last = max(st.done_s for st in sts)
    values = {
        "setup_s": setup_s,
        "query_geomean_ms": e2e.query_geomean_ms(lat),
        "queries_per_s": e2e.queries_per_s(
            sum(1 for st in sts if st.correct), first, last),
    }
    out = {}
    for m in bench["end_to_end"]:
        if not reported_in(m, cell_name):
            continue
        if values.get(m["name"]) is not None:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def scrape(servers) -> dict:
    return {role: supervisor.prometheus(supervisor.http_text(uri + "/v1/metrics"))
            for role, uri in servers.uris.items()}


def peak_bytes(info: dict):
    vals = [m.get("peak_bytes_in_use") for m in info.get("device_memory", [])
            if m.get("peak_bytes_in_use") is not None]
    return max(vals) if vals else None


def run(args, hooks: dict | None = None) -> int:
    """``hooks`` is for the tests under benchmarks/tests alone: it can
    skip the look for a chip and break the timed path underneath."""
    hooks = hooks or {}
    if not os.path.isdir(os.path.join(ROOT, "trino_tpu")):
        print("no program beside the benchmark: nothing to measure",
              file=sys.stderr)
        return 4
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg_entry = find_cell(bench, args.workload)
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = traffic.load_mix(cell["traffic"])
    rehearse = args.rehearse is not None
    schema = config["schema"]
    if rehearse:
        schema = args.rehearse or config["rehearsal"]["schema"]
    traced = bool(args.trace)
    workdir = os.path.join(WORK, args.workload)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    ctx = Context()
    ctx.cell, ctx.config, ctx.mix = cell, config, mix
    ctx.peaks = load_json(os.path.join(HERE, "peaks.json"))
    ctx.client_mod = hooks.get("client_mod") or supervisor.load_client()
    ctx.from_due = mix["loop"] == "open"
    ref = Reference(config, schema, mix)
    servers = supervisor.Servers(
        config, schema, workdir, traced,
        own_platform="cpu" if rehearse else None)
    ctx.servers = servers
    device_fault = None
    try:
        first = ref.missing()
        ref.start(workdir)
        if first:
            # one process generates the column cache; the servers read it
            log("reference: building tables and answers (first run here)")
            ref.wait("data", 900)
        servers.start()
        log(f"servers up: {list(servers.uris)}")
        # warm every statement text of the cell: tables onto the device,
        # every program out of the compile cache (or compiled, the first
        # time in a checkout)
        warm = loadgen.timed_client(ctx.client_mod, servers.entry_uri, 1500.0)
        for st in traffic.all_statements(mix):
            t = time.monotonic()
            warm.execute(st.sql)
            log(f"warmed {st.template} {list(st.params.values())} "
                f"in {time.monotonic() - t:.1f}s")
        # tables as the configuration states them, every answer on disk
        ref.wait("ready", 1200)
        # the device, from the server that owns the chip
        info = supervisor.http_json(servers.chip_uri + "/v1/info")
        if info.get("platform") != "tpu":
            device_fault = (f"the process that owns the chip reports platform "
                            f"{info.get('platform')!r}, not 'tpu'")
        elif info.get("device_count") != cell["chips"]:
            device_fault = (f"{info.get('device_count')} devices, the cell "
                            f"asks for {cell['chips']}")
        elif info.get("device_kind") not in ctx.peaks:
            device_fault = (f"device kind {info.get('device_kind')!r} is not "
                            f"in peaks.json")
        if device_fault and not (rehearse or hooks.get("skip_device_check")):
            log("FAILED at the device check: " + device_fault)
            return 3
        trace_dir = os.path.join(workdir, "trace")
        mark_wall_ns = None
        if traced:
            n_seen = len(servers.chip_child.lines)
            servers.chip_child.send(f"start {trace_dir}")
            line = servers.chip_child.wait_line("trace started", 120, n_seen)
            mark_wall_ns = int(line.split()[-1])
        ctx.before = scrape(servers)
        ctx.t0_wall_ns = time.time_ns()
        t_mono = time.monotonic()
        setup_s = t_mono - T_PROC
        log(f"window: {args.seconds}s of {cell['traffic']} (seed {args.seed})")
        # GET /v1/query keeps the last 200 finished statements: a traced
        # run reads it every few seconds so that none of a window's
        # statements is lost to a reader, however many the window holds
        seen_queries: dict = {}
        window_closed = threading.Event()

        def poll_query_list():
            while not window_closed.wait(5.0):
                try:
                    for q in supervisor.http_json(servers.entry_uri + "/v1/query"):
                        seen_queries[q.get("query_id")] = q
                except OSError:
                    pass

        poller = threading.Thread(target=poll_query_list, daemon=True)
        if traced:
            poller.start()
        try:
            ctx.statements, ctx.t0 = loadgen.window(
                ctx.client_mod, servers.entry_uri, mix, args.seed, args.seconds)
        finally:
            window_closed.set()
            if traced:
                poller.join(timeout=35)
        # the wall clock of any instant of the monotonic clock
        ctx.t0_wall_ns += int((ctx.t0 - t_mono) * 1e9)
        if traced:
            n_seen = len(servers.chip_child.lines)
            servers.chip_child.send("stop")
            servers.chip_child.wait_line("trace stopped", 300, n_seen)
        ctx.after = scrape(servers)
        ctx.info = supervisor.http_json(servers.chip_uri + "/v1/info")
        for q in supervisor.http_json(servers.entry_uri + "/v1/query"):
            seen_queries[q.get("query_id")] = q
        ctx.query_list = list(seen_queries.values())
        log(f"window closed: {len(ctx.statements)} statements")
        save_deltas(ctx, os.path.join(workdir, "counters.json"))
        if traced:
            import trace_reduce

            ctx.trace = trace_reduce.for_window(
                trace_dir, mark_wall_ns, ctx, os.path.join(workdir, "timeline.json"))
        layer_metrics = per_layer(bench, cell["name"], ctx) if traced else {}
    finally:
        killed = servers.stop()
        ref.stop()
    if killed:
        log(f"had to kill: {killed}")
    # the comparison: every answer of the window, once the program's
    # state is freed
    cmp_ = compare(mix, ref, ctx.statements)
    nums = cmp_["numbers"]
    nums["result_cache_hits"] = sum(
        ctx.after[r].get("trino_result_cache_hits_total", 0.0)
        - ctx.before[r].get("trino_result_cache_hits_total", 0.0)
        for r in ctx.after)
    correct = all(nums[k] <= LIMITS[k] for k in LIMITS)
    metrics = (layer_metrics if traced
               else end_to_end(bench, cell["name"], ctx, setup_s))
    device = {
        "platform": ctx.info.get("platform"),
        "kind": ctx.info.get("device_kind"),
        "count": ctx.info.get("device_count"),
        "memory_peak_bytes": peak_bytes(ctx.info),
    }
    result = {
        "correct": correct,
        "attempted": len(ctx.statements),
        "failed": nums["statements_failed"],
        "metrics": metrics,
        "device": device,
    }
    if traced and ctx.trace is not None:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    result["workload"] = cell["name"]
    result["seed"] = args.seed
    result["window_s"] = (max(s.done_s for s in ctx.statements)
                          - min(s.sent_s for s in ctx.statements))
    result["compared"] = {k: {"value": nums[k], "limit": LIMITS[k]}
                          for k in LIMITS}
    save_statements(ctx, os.path.join(workdir, "statements.jsonl"))
    for d in cmp_["details"]:
        log("compare: " + d)
    line = json.dumps(result)
    print("compared: " + json.dumps(result["compared"]), file=sys.stderr,
          flush=True)
    if device_fault:
        # rehearsal (or a test): everything ran, but no result is given
        # for a device that is not the chip
        log("FAILED at the device check: " + device_fault)
        print("rehearsal result (not a result): " + line, file=sys.stderr)
        if not hooks.get("skip_device_check"):
            return 3
    if killed:
        return 5
    print(line, flush=True)
    return 0


def save_deltas(ctx: Context, path: str) -> None:
    """Every exported series that moved in the window, by child."""
    moved = {
        role: {k: v - ctx.before[role].get(k, 0.0)
               for k, v in series.items()
               if v != ctx.before[role].get(k, 0.0)}
        for role, series in ctx.after.items()
    }
    with open(path, "w") as fh:
        json.dump(moved, fh, indent=1, sort_keys=True)


def save_statements(ctx: Context, path: str) -> None:
    """Every statement's template, pass and latency, for reading where
    the variation sits."""
    with open(path, "w") as fh:
        for st in ctx.statements:
            fh.write(json.dumps({
                "template": st.template, "params": st.params, "cls": st.cls,
                "group": st.group, "due_s": st.due_s,
                "sent_s": st.sent_s - ctx.t0,
                "sent_wall_s": ctx.t0_wall_ns / 1e9 + st.sent_s - ctx.t0,
                "done_s": st.done_s - ctx.t0,
                "latency_ms": ctx.latency_ms(st), "server_ms": st.server_ms,
                "error": st.error, "correct": st.correct,
            }) + "\n")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse", nargs="?", const="", default=None, metavar="SCHEMA",
        help="control-flow rehearsal on a CPU at the configuration's "
             "rehearsal schema (or SCHEMA): runs everything, prints no "
             "result and exits non-zero at the device check")
    return ap.parse_args(argv)


def _terminate(signum, frame):
    # a run that is told to stop still stops its children: the
    # ``finally`` of run() does it
    raise SystemExit(128 + signum)


if __name__ == "__main__":
    import signal

    signal.signal(signal.SIGTERM, _terminate)
    sys.exit(run(parse()))
