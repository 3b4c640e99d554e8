"""Spool integrity: per-file CRC32 headers, commit-manifest checksums,
quarantine, and end-to-end corruption recovery through the fleet.

The spool is the FTE durability tier — a committed stage output is
trusted as ground truth for retries, so silent bit rot there would
poison every downstream recovery. These tests flip real bytes in
committed partition files and require (a) detection at read time with
machine-parseable producer coordinates (SpoolCorruptionError), and
(b) the fleet treating corrupt exchange data as loss of the PRODUCING
task's output: quarantine the attempt, re-run the producer, and still
return oracle-exact results (the exchange-data-loss half of Trino's
task-retry model, not just consumer retry).
"""

import glob
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.connectors.tpch.connector import TpchConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.metadata import Metadata, Session
from trino_tpu.plan.fragment import fragment_plan
from trino_tpu.server.fleet import _CORRUPTION_RE, FleetRunner
from trino_tpu.exec import spool
from trino_tpu.testing.golden import (
    assert_rows_match,
    load_tpch_sqlite,
    to_sqlite,
)

#: a range of its own (19100+): until PR 48 this file spawned its two
#: workers on test_chaos.py's ports (18960+), and where xdist ran the two
#: files at once the later one's workers failed to bind while its
#: readiness probe found the other file's — two suites on one pair of
#: workers, and test_chaos.py's fetch-fault test failing now and then
BASE_PORT = 19100


def _page(n=64):
    payload = {
        "names": ["k", "v"],
        "types": [T.BIGINT, T.DOUBLE],
        "cols": [
            (np.arange(n, dtype=np.int64), None),
            (np.linspace(0.0, 1.0, max(n, 1))[:n], None),
        ],
    }
    return spool.host_to_page(payload)


def _write(root, n=64, attempt=0):
    spool.write_task_output(
        root, "7", "s7t0", attempt, _page(n), "hash", ["k"], 4
    )


def _flip_bytes(path, offset=None, count=4):
    size = os.path.getsize(path)
    if offset is None:
        offset = size // 2
    with open(path, "r+b") as f:
        f.seek(offset)
        chunk = f.read(count)
        f.seek(offset)
        f.write(bytes(b ^ 0xFF for b in chunk))


# ---- unit: file-level detection -------------------------------------------


def test_spool_roundtrip_verifies_clean(tmp_path):
    root = str(tmp_path)
    _write(root)
    got = spool.read_partition(root, "7", ["s7t0"], None)
    assert got["names"] == ["k", "v"]
    assert len(got["cols"][0][0]) == 64
    assert sorted(got["cols"][0][0].tolist()) == list(range(64))


def test_spool_detects_flipped_body_bytes(tmp_path):
    root = str(tmp_path)
    _write(root)
    victim = sorted(glob.glob(str(tmp_path / "stage-7" / "*.npz")))[0]
    _flip_bytes(victim)
    with pytest.raises(spool.SpoolCorruptionError) as ei:
        spool.read_partition(root, "7", ["s7t0"], None)
    e = ei.value
    assert e.stage_id == "7" and e.task_id == "s7t0" and e.attempt == 0
    assert os.path.basename(victim) in str(e)


def test_spool_detects_header_tamper_and_truncation(tmp_path):
    root = str(tmp_path)
    _write(root)
    files = sorted(glob.glob(str(tmp_path / "stage-7" / "*.npz")))
    _flip_bytes(files[0], offset=0)  # magic/CRC header
    with pytest.raises(spool.SpoolCorruptionError):
        spool.read_partition(root, "7", ["s7t0"], None)
    _write(root)  # restore (rewrites every partition file)
    with open(files[0], "r+b") as f:
        f.truncate(os.path.getsize(files[0]) // 2)
    with pytest.raises(spool.SpoolCorruptionError):
        spool.read_partition(root, "7", ["s7t0"], None)


def test_spool_detects_missing_partition_file(tmp_path):
    root = str(tmp_path)
    _write(root)
    victim = sorted(glob.glob(str(tmp_path / "stage-7" / "*.npz")))[0]
    os.unlink(victim)
    with pytest.raises(spool.SpoolCorruptionError, match="missing"):
        spool.read_partition(root, "7", ["s7t0"], None)


def test_spool_done_marker_carries_manifest(tmp_path):
    root = str(tmp_path)
    _write(root)
    # the attempt-level manifest marker, not the per-partition
    # -p{N}.done markers pipelined admission also commits
    (marker,) = [
        p for p in glob.glob(str(tmp_path / "stage-7" / "*.done"))
        if "-p" not in os.path.basename(p)
    ]
    meta = json.load(open(marker))
    files = {
        os.path.basename(p)
        for p in glob.glob(str(tmp_path / "stage-7" / "*.npz"))
    }
    assert set(meta["files"]) == files
    assert all(isinstance(c, int) for c in meta["files"].values())
    assert sorted(meta["partitions"]) == sorted(
        int(n.rsplit("-p", 1)[1][:-4]) for n in files
    )


def test_spool_quarantine_and_next_attempt(tmp_path):
    root = str(tmp_path)
    _write(root, attempt=0)
    assert spool.committed_attempt(root, "7", "s7t0") == 0
    assert spool.next_attempt(root, "7", "s7t0") == 1
    assert spool.quarantine_attempt(root, "7", "s7t0", 0) is True
    assert spool.committed_attempt(root, "7", "s7t0") is None
    # idempotent; the withdrawn attempt still blocks its number
    assert spool.quarantine_attempt(root, "7", "s7t0", 0) is False
    assert spool.next_attempt(root, "7", "s7t0") == 1
    _write(root, attempt=1)
    assert spool.committed_attempt(root, "7", "s7t0") == 1
    got = spool.read_partition(root, "7", ["s7t0"], None)
    assert sorted(got["cols"][0][0].tolist()) == list(range(64))


def test_spool_quarantine_retracts_partition_markers(tmp_path):
    """Regression: quarantining an attempt must withdraw its
    per-partition ``-p{N}.done`` markers along with the attempt-level
    manifest marker — a stale partition marker would let pipelined
    admission re-admit a consumer against the quarantined data."""
    root = str(tmp_path)
    _write(root, attempt=0)
    parts = spool.committed_partitions(root, "7", "s7t0", 0)
    assert parts, "writer committed no partition markers"
    assert spool.quarantine_attempt(root, "7", "s7t0", 0) is True
    assert spool.committed_partitions(root, "7", "s7t0", 0) == []
    # the evidence trail survives as .done.bad for every marker tier
    bad = glob.glob(str(tmp_path / "stage-7" / "*.done.bad"))
    assert len(bad) == 1 + len(parts)
    # a pinned read against the quarantined attempt now refuses
    with pytest.raises(spool.SpoolCorruptionError):
        spool.read_partition(
            root, "7", ["s7t0"], parts[0], attempts={"s7t0": 0}
        )


def test_corruption_error_is_machine_parseable(tmp_path):
    """The fleet maps a worker-serialized SpoolCorruptionError back to
    the producing task via _CORRUPTION_RE; the error text and the
    regex must stay in lockstep."""
    root = str(tmp_path)
    _write(root)
    victim = sorted(glob.glob(str(tmp_path / "stage-7" / "*.npz")))[0]
    _flip_bytes(victim)
    with pytest.raises(spool.SpoolCorruptionError) as ei:
        spool.read_partition(root, "7", ["s7t0"], None)
    serialized = f"{type(ei.value).__name__}: {ei.value}"
    m = _CORRUPTION_RE.search(serialized)
    assert m is not None, serialized
    assert m.group(1) == "7"
    assert m.group(2) == "s7t0"
    assert int(m.group(3)) == 0


# ---- fleet: end-to-end corruption recovery --------------------------------


def _spawn_worker(port: int) -> subprocess.Popen:
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "trino_tpu.server.worker",
            "--port", str(port),
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    deadline = time.monotonic() + 120
    while True:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/info", timeout=1
            ) as resp:
                json.loads(resp.read())
                return proc
        except Exception:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"worker died: {proc.stdout.read()[:4000]}"
                )
            if time.monotonic() > deadline:
                proc.kill()
                raise TimeoutError("worker did not come up")
            time.sleep(0.3)


@pytest.fixture(scope="module")
def workers():
    procs = [_spawn_worker(BASE_PORT + i) for i in range(2)]
    yield [f"http://127.0.0.1:{BASE_PORT + i}" for i in range(2)]
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


@pytest.fixture(scope="module")
def oracle():
    data = QueryRunner.tpch("tiny").metadata.connector("tpch").data("tiny")
    return load_tpch_sqlite(data)


@pytest.fixture()
def fleet(workers, tmp_path):
    md = Metadata()
    md.register_catalog("tpch", TpchConnector())
    return FleetRunner(
        workers, md, Session(catalog="tpch", schema="tiny"),
        spool_root=str(tmp_path), n_partitions=4,
    )


def test_fleet_reruns_producer_after_spool_corruption(fleet, oracle):
    """Corrupt one committed partition file the moment its stage
    completes (before any consumer reads it). The consumer's read must
    fail with producer coordinates, the fleet must quarantine the
    attempt and re-run the PRODUCING task at the next attempt number,
    and the query must still be oracle-exact."""
    state = {"corrupted": None}

    def stage_hook(sid):
        if state["corrupted"] is not None:
            return
        files = sorted(glob.glob(os.path.join(
            fleet.spool_root, "*", f"stage-{sid}", "*-a0-p*.npz"
        )))
        if not files:
            return
        _flip_bytes(files[0])
        state["corrupted"] = files[0]

    fleet.stage_hook = stage_hook
    fleet.keep_spool = True  # inspect quarantine state after the query
    # pin the stage barrier: this scenario requires the consumer to
    # read AFTER the corruption hook fires at stage completion; under
    # PIPELINED the consumer may legitimately finish its (CRC-valid)
    # read before the hook ever corrupts the file
    fleet.session.properties["stage_admission"] = "BARRIER"
    sql = (
        "select o_orderpriority, count(*) from orders "
        "group by o_orderpriority order by 1"
    )
    result = fleet.execute(sql)
    assert state["corrupted"] is not None, "no stage output to corrupt"
    # producer re-run + consumer retry both went through the retry path
    assert result.tasks_retried >= 1
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(
        result.rows, expected, ordered=result.ordered, abs_tol=1e-9
    )
    # the corrupt attempt was withdrawn, a clean one recommitted
    stage_dir = os.path.dirname(state["corrupted"])
    assert glob.glob(os.path.join(stage_dir, "*.done.bad"))


def test_fleet_recovers_root_corruption_at_coordinator(fleet, oracle):
    """Corrupt the ROOT stage's committed output after _run_dag has
    moved past it: the coordinator's own result read must detect it,
    quarantine, synchronously re-run the producing task, and read the
    clean recommit."""
    sql = (
        "select o_orderpriority, count(*) from orders "
        "group by o_orderpriority order by 1"
    )
    root_sid = fragment_plan(
        fleet._planner.plan_sql(sql)
    )[-1].stage_id
    state = {"corrupted": None}

    def stage_hook(sid):
        if sid != root_sid or state["corrupted"] is not None:
            return
        files = sorted(glob.glob(os.path.join(
            fleet.spool_root, "*", f"stage-{sid}", "*-a0-p*.npz"
        )))
        _flip_bytes(files[0])
        state["corrupted"] = files[0]

    fleet.stage_hook = stage_hook
    result = fleet.execute(sql)
    assert state["corrupted"] is not None, "root stage never corrupted"
    assert result.tasks_retried >= 1
    expected = oracle.execute(to_sqlite(sql)).fetchall()
    assert_rows_match(
        result.rows, expected, ordered=result.ordered, abs_tol=1e-9
    )
