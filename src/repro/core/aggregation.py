"""Distributed group-by aggregation (paper §V).

The aggregator is itself a MapReduce round: map emits ``((group_keys),
p)``, the shuffle routes groups to their owning reducer, reduce sums.
Cost charged: read |input| + shuffle |input| (the paper's ``2·|input|``
term), unless a *combiner* (local pre-aggregation before the shuffle —
a beyond-paper optimization, off by default for faithfulness) shrinks
the shuffled side.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import hashing
from .local import groupby_sum
from .relation import Relation
from .shuffle import Grid, add_fill, buffer_fill, shuffle_by_bucket


@jax.named_scope("join.groupby")
def distributed_groupby_sum(grid: Grid, rel: Relation, keys: Sequence[str],
                            value: str, *, recv_capacity: int,
                            out_capacity: int, local_capacity: int | None = None,
                            local_combine: bool = False,
                            segment_backend: str = "auto",
                            ) -> Tuple[Relation, Dict[str, jnp.ndarray], jnp.ndarray]:
    """SUM(value) GROUP BY keys across the grid.

    Groups are routed by hashing the key tuple, one hop per grid axis;
    every device then owns complete groups and aggregates locally via
    the single-pass :func:`repro.core.local.groupby_sum` (one composite
    sort + the ``segment_sum`` kernel; ``segment_backend`` forwards to
    its kernel dispatch — Pallas on TPU, jnp oracle elsewhere).

    local_combine=True runs the combiner (local pre-aggregation) before
    the shuffle — Hadoop's combiner, which the paper does NOT model;
    kept off for paper-faithful accounting.
    """
    keys = tuple(keys)
    n_in = grid.reduce_sum(grid.map_devices(lambda r: r.count(), rel))
    overflow = jnp.zeros((), jnp.bool_)

    cur = rel
    fills = []
    if local_combine:
        def combine(r: Relation):
            return groupby_sum(r, keys, value, backend=segment_backend)
        cur, ovf_c = grid.map_devices(combine, cur)
        overflow = overflow | jnp.any(grid.reduce_any(ovf_c))
        fills.append(buffer_fill(grid, cur))

    def key_bucket(r: Relation, n_buckets: int, salt: int) -> jnp.ndarray:
        mixed = r.col(keys[0])
        for i, k in enumerate(keys[1:]):
            mixed = mixed ^ hashing.bucket_hash(r.col(k), 1 << 30, salt=2 + i)
        return hashing.bucket_hash(mixed, n_buckets, salt=salt)

    for axis in range(len(grid.shape)):
        if grid.shape[axis] == 1:
            continue  # clamped axis: a single owner, the hop is a no-op
        bucket = grid.map_devices(
            lambda r, _a=axis: key_bucket(r, grid.shape[_a], salt=_a), cur)
        cur, ovf, fill = shuffle_by_bucket(grid, cur, bucket, axis,
                                           recv_capacity,
                                           local_capacity=local_capacity)
        overflow = overflow | ovf
        fills.append(fill)

    shuffled = grid.reduce_sum(grid.map_devices(lambda r: r.count(), cur))

    def reduce_side(r: Relation):
        return groupby_sum(r, keys, value, out_capacity,
                           backend=segment_backend)

    agg, ovf_a = grid.map_devices(reduce_side, cur)
    overflow = overflow | jnp.any(grid.reduce_any(ovf_a))

    stats = {
        "read": n_in.astype(jnp.float32),
        "shuffled": shuffled.astype(jnp.float32),
        **add_fill(*fills, buffer_fill(grid, agg)),
    }
    return agg, stats, overflow


@jax.named_scope("join.groupby")
def project_product(grid: Grid, rel: Relation, keys: Sequence[str],
                    value_cols: Sequence[str], out_name: str = "p") -> Relation:
    """Map phase of the aggregator: emit (keys, prod(value_cols)) —
    e.g. ((a,c), v·w) for matrix multiplication."""
    keys = tuple(keys)

    def proj(r: Relation):
        p = jnp.ones_like(r.col(value_cols[0]).astype(jnp.float32))
        for vc in value_cols:
            p = p * r.col(vc).astype(jnp.float32)
        cols = {k: r.col(k) for k in keys}
        cols[out_name] = p
        return Relation(cols, r.valid)

    return grid.map_devices(proj, rel)
