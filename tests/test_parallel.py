"""Distributed aggregation over the virtual 8-device mesh.

The analog of the reference's DistributedQueryRunner tier
(TESTING/DistributedQueryRunner.java:98): real collectives over N
devices in one process, checked against a host oracle.
"""

import collections

import numpy as np
import jax.numpy as jnp
import pytest

from trino_tpu import types as T
from trino_tpu.connectors.base import TableSchema
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.engine import QueryRunner
from trino_tpu.metadata import Metadata, Session
from trino_tpu.parallel.core import WORKER_AXIS, make_mesh
from trino_tpu.parallel.exchange import partition_exchange

import jax


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    return make_mesh(8)


def _mesh_runner(mesh, columns, arrays):
    """A mesh QueryRunner over one memory table ``t``: the path a
    distributed GROUP BY really takes (exec/mesh.py: PARTIAL aggregate
    per shard -> hash exchange on the key -> FINAL on the owner)."""
    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    conn = md.connector("memory")
    conn.create_table("default", "t", TableSchema("t", columns))
    conn.insert("default", "t", arrays)
    return QueryRunner(
        md, Session(catalog="memory", schema="default"), mesh=mesh
    )


def test_distributed_group_sums(mesh):
    rng = np.random.default_rng(0)
    n = 1024
    keys = rng.integers(0, 37, n).astype(np.int64)
    vals = rng.integers(0, 100, n).astype(np.int64)
    live = np.ones(n, dtype=bool)
    live[::13] = False

    r = _mesh_runner(
        mesh,
        [("k", T.BIGINT), ("v", T.BIGINT), ("live", T.BOOLEAN)],
        {"k": keys, "v": vals, "live": live},
    )
    rows = r.execute(
        "select k, sum(v), count(*) from t where live group by k"
    ).rows
    # the aggregation crossed the mesh: one hash exchange on the key
    assert r.executor.exchange_stats["exchanges"] == 1

    got = {}
    for k, s, c in rows:
        assert k not in got, f"key {k} finalized on two devices"
        got[int(k)] = (int(s), int(c))

    want_s = collections.Counter()
    want_c = collections.Counter()
    for k, v, lv in zip(keys, vals, live):
        if lv:
            want_s[int(k)] += int(v)
            want_c[int(k)] += 1
    assert got == {k: (want_s[k], want_c[k]) for k in want_s}


def test_distributed_group_sums_with_nulls(mesh):
    rng = np.random.default_rng(1)
    n = 512
    keys = rng.integers(0, 5, n).astype(np.int64)
    valid = rng.random(n) > 0.2  # NULL keys group together
    vals = np.ones(n, dtype=np.int64)

    r = _mesh_runner(
        mesh, [("k", T.BIGINT), ("v", T.BIGINT)],
        {"k": (keys, valid), "v": vals},
    )
    rows = r.execute("select k, sum(v), count(*) from t group by k").rows
    assert r.executor.exchange_stats["exchanges"] == 1
    null_groups = [int(c) for k, s, c in rows if k is None]
    assert len(null_groups) == 1
    assert null_groups[0] == int((~valid).sum())
    want = collections.Counter(int(k) for k in keys[valid])
    assert {int(k): int(s) for k, s, c in rows if k is not None} == want


def test_partition_exchange_overflow_detected(mesh):
    n = 64

    def step(dest, live, vals):
        out, rlive, ovf = partition_exchange(
            dest, live, {"v": vals}, 8, 2, WORKER_AXIS
        )
        return jax.lax.pmax(ovf.astype(jnp.int32), WORKER_AXIS)

    from jax.sharding import PartitionSpec as P

    f = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(WORKER_AXIS), P(WORKER_AXIS), P(WORKER_AXIS)),
        out_specs=P(),
        check_vma=False,
    ))
    # every row targets partition 0 with bucket capacity 2 -> overflow
    dest = jnp.zeros(n, dtype=jnp.int32)
    live = jnp.ones(n, dtype=jnp.bool_)
    vals = jnp.arange(n, dtype=jnp.int64)
    assert int(f(dest, live, vals)) == 1


def test_skew_join_hot_key():
    """A 90%-one-key probe side must join correctly on the mesh: the
    hot destination splits (probe salted round-robin, its build rows
    broadcast) instead of escalating one bucket to shard capacity and
    failing (SkewedPartitionRebalancer analog for joins)."""
    import numpy as np

    from trino_tpu.connectors.memory import MemoryConnector
    from trino_tpu.engine import QueryRunner
    from trino_tpu.metadata import Metadata, Session
    from trino_tpu.parallel.core import make_mesh

    md = Metadata()
    md.register_catalog("memory", MemoryConnector())
    conn = md.connector("memory")
    n = 100_000
    rng = np.random.default_rng(5)
    keys = np.where(rng.random(n) < 0.9, 7, rng.integers(0, 1000, n))
    vals = np.arange(n)
    from trino_tpu import types as T
    from trino_tpu.connectors.base import TableSchema

    conn.create_table("default", "probe", TableSchema(
        "probe", [("k", T.BIGINT), ("v", T.BIGINT)]
    ))
    conn.insert("default", "probe", {
        "k": keys.astype(np.int64), "v": vals.astype(np.int64),
    })
    conn.create_table("default", "build", TableSchema(
        "build", [("k", T.BIGINT), ("w", T.BIGINT)]
    ))
    conn.insert("default", "build", {
        "k": np.arange(0, 1000, dtype=np.int64),
        "w": np.arange(0, 1000, dtype=np.int64) * 10,
    })
    r = QueryRunner(
        md, Session(catalog="memory", schema="default"),
        mesh=make_mesh(),
    )
    # force the partitioned path (broadcast would dodge the skew)
    r.session.properties["join_distribution_type"] = "PARTITIONED"
    got = r.execute(
        "select count(*), sum(w) from probe, build where probe.k = build.k"
    ).rows
    expect_count = len(keys)
    expect_sum = int(np.sum(keys * 10))
    assert got == [(expect_count, expect_sum)]
    assert r.executor.skew_joins >= 1  # the split actually engaged
