"""Distributed two-way hash join — the building block of the 2,3J cascade.

MapReduce mapping (paper §III): the map phase emits ``(h(b), tuple)``;
here that is a local hash-partition + shuffle to the device owning
bucket ``h(b)``; the reduce phase is the per-device ``local_join``.

Communication-cost accounting follows the paper exactly: each round
charges (tuples read by mappers) + (tuples shuffled to reducers); final
output writes are never charged.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp

from . import hashing
from .local import local_join
from .relation import Relation
from .shuffle import (Grid, add_fill, buffer_fill, compact_to, concat_rows,
                      shuffle_by_bucket, split_rows)


def flat_grid_bucket(grid: Grid, key: jnp.ndarray, salt: int = 0) -> Tuple[jnp.ndarray, ...]:
    """Hash a key column into one bucket index per grid axis, such that the
    flattened bucket enumerates all k = prod(grid.shape) devices."""
    k_total = 1
    for s in grid.shape:
        k_total *= s
    flat = hashing.bucket_hash(key, k_total, salt=salt)
    idxs = []
    rem = flat
    for s in reversed(grid.shape):
        idxs.append(rem % s)
        rem = rem // s
    return tuple(reversed(idxs))


def shuffle_to_device(grid: Grid, rel: Relation, key: str, recv_capacity: int,
                      salt: int = 0, local_capacity: int | None = None):
    """Route every tuple to the unique device owning hash(key) — one hop per
    grid axis (multi-hop routing on >1-D grids, same final guarantee).
    After each hop the receive buffers are compacted to
    ``local_capacity`` (the reducer memory budget).  Returns (rel,
    overflow, the live-row counter of every hop's buffers)."""
    overflow = jnp.zeros((), jnp.bool_)
    cur = rel
    fills = []
    for axis in range(len(grid.shape)):
        def bucketize(r: Relation, _axis=axis):
            return flat_grid_bucket(grid, r.col(key), salt=salt)[_axis]

        bucket = grid.map_devices(bucketize, cur)
        cur, ovf, fill = shuffle_by_bucket(grid, cur, bucket, axis,
                                           recv_capacity,
                                           local_capacity=local_capacity)
        overflow = overflow | ovf
        fills.append(fill)
    return cur, overflow, add_fill(*fills)


def two_way_join(grid: Grid, left: Relation, right: Relation,
                 left_key: str, right_key: str, *,
                 recv_capacity: int, out_capacity: int,
                 local_capacity: int | None = None,
                 prefix_l: str = "", prefix_r: str = "",
                 salt: int = 0, join_impl: str = "sort_merge",
                 overlap_chunks: int = 1,
                 ) -> Tuple[Relation, Dict[str, jnp.ndarray], jnp.ndarray]:
    """R ⋈ S on left_key == right_key across the whole grid.

    Returns (per-device join shards, stats, overflow).  stats counts
    tuples in the paper's units: ``read`` (map input) and ``shuffled``
    (map output received by reducers) — cost of this round is their sum
    — and the live-row counter of the round's buffers (``live_rows``,
    ``buffer_rows``).

    ``join_impl`` selects the reduce-side kernel: ``"sort_merge"``
    (default, the sorted-probe fast path), ``"fused"`` (the rank-packed
    pipeline), or ``"all_pairs"`` (the quadratic oracle) — same tuple
    set, stats, and overflow either way.

    ``overlap_chunks > 1`` selects the overlapped schedule: the right
    side is split into that many row blocks, each shuffled and joined
    against the resident left shard independently, so block b+1's
    all-to-all carries no dependency on block b's join and XLA overlaps
    them.  The blocks partition the rows, so ``stats`` and the overflow
    condition are exactly those of the staged schedule; only the output
    row order within a device may differ (same tuple multiset — the
    per-chunk outputs are concatenated and compacted to
    ``out_capacity``).
    """
    n_left = grid.reduce_sum(grid.map_devices(lambda r: r.count(), left))
    n_right = grid.reduce_sum(grid.map_devices(lambda r: r.count(), right))

    left_s, ovf_l, fill = shuffle_to_device(grid, left, left_key,
                                            recv_capacity, salt,
                                            local_capacity)

    def reduce_side(l: Relation, r: Relation):
        return local_join(l, r, left_key, right_key, out_capacity,
                          prefix_l=prefix_l, prefix_r=prefix_r,
                          impl=join_impl)

    def shard_count(rel):
        return grid.reduce_sum(grid.map_devices(lambda r: r.count(), rel))

    if overlap_chunks <= 1:
        right_s, ovf_r, fill_r = shuffle_to_device(grid, right, right_key,
                                                   recv_capacity, salt,
                                                   local_capacity)
        joined, ovf_j = grid.map_devices(reduce_side, left_s, right_s)
        overflow = ovf_l | ovf_r | jnp.any(grid.reduce_any(ovf_j))
        received = shard_count(left_s) + shard_count(right_s)
        fill = add_fill(fill, fill_r, buffer_fill(grid, joined))
    else:
        overflow = ovf_l
        received = shard_count(left_s)
        parts = []
        for chunk in split_rows(right, overlap_chunks):
            chunk_s, ovf_c, fill_c = shuffle_to_device(
                grid, chunk, right_key, recv_capacity, salt, local_capacity)
            received = received + shard_count(chunk_s)
            out_c, ovf_j = grid.map_devices(reduce_side, left_s, chunk_s)
            overflow = overflow | ovf_c | jnp.any(grid.reduce_any(ovf_j))
            parts.append(out_c)
            fill = add_fill(fill, fill_c)
        # Per-chunk matches are a subset of the full hop's, so the chunk
        # joins at out_capacity cannot overflow unless the staged hop
        # would; the final compaction reimposes the staged capacity and
        # its overflow condition (total matches > out_capacity).
        joined, ovf_cc = compact_to(grid, concat_rows(parts), out_capacity)
        overflow = overflow | ovf_cc
        fill = add_fill(fill, buffer_fill(grid, *parts, joined))
    stats = {
        "read": (n_left + n_right).astype(jnp.float32),
        "shuffled": received.astype(jnp.float32),
        **fill,
    }
    return joined, stats, overflow
