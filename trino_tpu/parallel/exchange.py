"""Hash exchange over the device mesh.

The TPU-native replacement for the reference's shuffle subsystem
(PartitionedOutputOperator -> OutputBuffer -> HTTP long-poll ->
DirectExchangeClient, SURVEY.md §3.4): rows are routed to their owning
device with one ``lax.all_to_all`` over ICI instead of serialize +
HTTP + deserialize. No serde exists at all — device arrays stay device
arrays.

Shapes are static: each shard scatters its rows into ``n`` fixed-size
buckets (one per destination device) and the all_to_all swaps bucket i
of shard j with bucket j of shard i. Bucket overflow is detected and
reported per shard (the analog of output-buffer backpressure; callers
re-run with a bigger bucket or pre-aggregate harder).

Rows that already lie ranged and ordered on the exchange's one key need
none of that: ``seam_exchange`` moves only the runs a shard boundary
cuts, to the neighbouring shard, by contiguous copies.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from trino_tpu.exec.kernels import kernel

__all__ = ["partition_exchange", "seam_exchange"]


@kernel
def partition_exchange(
    dest: jnp.ndarray,
    live: jnp.ndarray,
    payload: dict[str, jnp.ndarray],
    n_partitions: int,
    bucket_capacity: int,
    axis: str,
):
    """Route rows to devices by ``dest`` with one all_to_all.

    Must be called inside shard_map over ``axis``. ``dest[i]`` in
    [0, n_partitions) is row i's owning device; dead rows are dropped.

    Returns (received payload dict of [n_partitions * bucket_capacity]
    arrays, received live mask, overflowed: scalar bool — True when a
    bucket was too small and rows were dropped).

    The scatter is the PagePartitioner analog
    (MAIN/operator/output/PagePartitioner.java:134): a rank-per-
    destination prefix sum replaces the per-row appender loop.
    """
    n = dest.shape[0]
    # position of each row within its destination bucket: prefix count
    # of same-destination rows (one-hot cumsum, vectorized appender)
    one_hot = (
        (dest[:, None] == jnp.arange(n_partitions)[None, :]) & live[:, None]
    )
    rank = jnp.cumsum(one_hot.astype(jnp.int32), axis=0) - one_hot.astype(
        jnp.int32
    )
    pos = jnp.take_along_axis(rank, jnp.clip(dest, 0, n_partitions - 1)[:, None], axis=1)[:, 0]
    counts = jnp.sum(one_hot, axis=0)
    overflowed = jnp.any(counts > bucket_capacity)

    in_range = live & (pos < bucket_capacity)
    flat_idx = jnp.where(
        in_range, dest * bucket_capacity + pos, n_partitions * bucket_capacity
    )

    out = {}
    for name, arr in payload.items():
        trailing = arr.shape[1:]  # two-limb decimal columns are [n, 2]
        buckets = jnp.zeros(
            (n_partitions * bucket_capacity,) + trailing, dtype=arr.dtype
        ).at[flat_idx].set(arr, mode="drop")
        buckets = buckets.reshape(
            (n_partitions, bucket_capacity) + trailing
        )
        # swap bucket p of this shard with bucket <this> of shard p
        received = jax.lax.all_to_all(
            buckets, axis, split_axis=0, concat_axis=0, tiled=False
        )
        out[name] = received.reshape((-1,) + trailing)
    sent_live = jnp.zeros(
        (n_partitions * bucket_capacity,), dtype=jnp.bool_
    ).at[flat_idx].set(True, mode="drop")
    sent_live = sent_live.reshape(n_partitions, bucket_capacity)
    recv_live = jax.lax.all_to_all(
        sent_live, axis, split_axis=0, concat_axis=0, tiled=False
    ).reshape(-1)
    return out, recv_live, overflowed


@kernel
def seam_exchange(
    word: jnp.ndarray,
    live: jnp.ndarray,
    leaves: list[jnp.ndarray],
    n_partitions: int,
    bucket: int,
    axis: str,
):
    """The exchange on a key the shards are ranged and ordered on, in
    place: afterwards every key's live rows are on one shard, every
    shard's live rows still an ascending prefix.

    Must be called inside shard_map over ``axis``. ``word`` is the
    key's normalized bits (uint64, compared unsigned: the order
    ``kernels.run_group`` checks), ``live`` the shard's mask, ``leaves``
    every column lane to be kept with its row. A shard whose leading
    run continues the last key of the shard before it hands that run —
    the first ``bucket`` rows of every leaf, one ``ppermute`` each — to
    that shard, which writes it behind its live rows; the sender shifts
    left by what it sent. Slices, concatenations and in-place updates
    only: no sort, gather, scatter or one-hot over the rows.

    Returns (leaves', live', stat): ``stat`` is int32
    ``[failed, rows moved, live rows of shard 0, 1, ...]``, the same on
    every shard. ``failed`` says a precondition does not hold and the
    outputs mean nothing (the caller exchanges by hash instead): some
    shard's live rows are not an ascending prefix, a shard starts below
    the rows before it, a key lies on more than two shards or across an
    empty one, a leading run is longer than ``bucket``, or a receiver
    has no room behind its live rows.
    """
    n, cap = n_partitions, live.shape[0]
    at0 = jnp.arange(cap, dtype=jnp.int32) == 0
    broken = jnp.any(
        live & ~at0 & (~jnp.roll(live, 1) | (word < jnp.roll(word, 1)))
    )
    n_live = jnp.sum(live.astype(jnp.int32))
    head = word[0]
    tail = jax.lax.dynamic_index_in_dim(
        word, jnp.maximum(n_live - 1, 0), keepdims=False
    )
    # ascending: the rows that hold the first key are the leading run
    run = jnp.sum((live & (word == head)).astype(jnp.int32))
    ends = jax.lax.all_gather(jnp.stack([head, tail]), axis)
    nums = jax.lax.all_gather(
        jnp.stack([n_live, run, broken.astype(jnp.int32)]), axis
    )
    heads, tails = ends[:, 0], ends[:, 1]
    lives, runs = nums[:, 0], nums[:, 1]
    held = lives > 0
    # shard i hands its leading run to shard i-1
    sends = jnp.concatenate([
        jnp.zeros((1,), jnp.bool_),
        held[1:] & held[:-1] & (heads[1:] == tails[:-1]),
    ])
    sent = jnp.where(sends, runs, 0)
    got = jnp.concatenate([sent[1:], jnp.zeros((1,), jnp.int32)])
    after = lives - sent + got
    failed = (
        jnp.any(nums[:, 2] > 0)
        | jnp.any(sends & (runs > bucket))
        | jnp.any(after > cap)
        # a shard that is one run, handing it on and being handed more
        # of it: the key is on three shards
        | jnp.any(sends[1:-1] & sends[2:] & (heads[1:-1] == tails[1:-1]))
    )
    # each shard against the last rows before it, empty shards skipped
    # (n is the mesh's size: a handful of scalar steps)
    seen, before = jnp.bool_(False), jnp.uint64(0)
    for i in range(n):
        failed = failed | (
            held[i] & seen & (
                (heads[i] < before) | ((heads[i] == before) & ~sends[i])
            )
        )
        before = jnp.where(held[i], tails[i], before)
        seen = seen | held[i]
    me = jax.lax.axis_index(axis)
    out = []
    for leaf in leaves:
        handed = jax.lax.ppermute(
            leaf[:bucket], axis, perm=[(i, i - 1) for i in range(1, n)]
        )
        wide = jnp.concatenate([
            leaf, jnp.zeros((bucket,) + leaf.shape[1:], leaf.dtype)
        ])
        wide = jax.lax.dynamic_update_slice_in_dim(
            wide, handed, lives[me], axis=0
        )
        out.append(
            jax.lax.dynamic_slice_in_dim(wide, sent[me], cap, axis=0)
        )
    new_live = jnp.arange(cap, dtype=jnp.int32) < after[me]
    stat = jnp.concatenate([
        jnp.stack([failed.astype(jnp.int32), jnp.sum(sent)]), after
    ])
    return out, new_live, stat
