"""Physical executor: lower a :class:`JoinQuery` onto a reducer Grid.

The lowerings are written once for *any* connected query hypergraph —
chains, cycles (triangles), stars, cliques — and run on either grid
backend (SimGrid / ShardGrid):

* :func:`one_round_query` — the Afrati–Ullman *Shares* join on a
  hypercube with one dimension per join attribute.  Relation R_j pins
  the dims of its own join attributes and is replicated
  (``broadcast_along``) over every other dim — the generalization of
  1,3J's "S to one device, R to its row, T to its column".  The reduce
  side chains local joins along a connected left-deep order; when a
  hop closes a cycle (the incoming relation shares more than one
  attribute with the accumulated result), the extra equalities are
  applied as post-join *filters* at that hop.  For a chain on its
  (N−1)-dim grid this is bit-for-bit the historical
  :func:`one_round_chain` (kept as a thin alias).

* :func:`cascade_query` — the left-deep cascade of ``two_way_join``
  rounds along a planner-chosen join order, cycle-closing predicates
  again filtering at the closing hop; aggregated queries run one final
  charged aggregation round.  Chain queries with endpoint aggregates
  should use :func:`cascade_chain`, which adds the paper's aggregation
  *pushdown* (sound only for chains) after every non-final round.

* :func:`shares_skew_chain` — the skew-aware *SharesSkew* union: one
  Shares sub-join per heavy/residual combination of the join
  attributes, each on the plain hypercube with its heavy dims clamped
  to share 1 (heavy tuples broadcast there).  Driven by a
  :class:`repro.core.skew.SkewSplitPlan`; SimGrid only; chains only.

Every lowering takes a ``join_impl`` knob selecting the reduce-side
join kernel — ``"sort_merge"`` (default, the sorted-probe data plane)
or ``"all_pairs"`` (the quadratic oracle) — and
:func:`jit_execute_query` / :func:`jit_execute_chain` compile a whole
(plan, caps) execution into one cached XLA program with donated input
buffers, instead of per-hop dispatch.

Cost accounting is paper-faithful and identical to the three-way
implementations: each round charges read + shuffled tuples; the final
aggregator of a pushdown cascade is uncharged unless requested.

Map-phase bucket histograms (per-reducer load, the skew diagnostic)
are routed through the Pallas ``hash_histogram`` kernel on TPU and a
jnp scatter-add elsewhere — see ``repro.kernels.hash_partition
.bucket_counts``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import config
from ..kernels.hash_partition import bucket_counts
from . import hashing
from .aggregation import distributed_groupby_sum, project_product
from .cost_model import ChainStats, chain_replications
from .local import groupby_sum, local_join
from .plan import ChainQuery, JoinQuery
from .relation import Relation, concat
from .shuffle import (FILL_KEYS, Grid, SimGrid, add_fill, broadcast_along,
                      buffer_fill, compact_to, concat_rows, shuffle_by_bucket,
                      split_rows)
from .two_way import two_way_join

Stats = Dict[str, jnp.ndarray]


@dataclasses.dataclass(frozen=True)
class ChainCaps:
    """Static buffer budgets for one chain-query execution.

    recv:  per-(device, source) slot capacity of every shuffle hop.
    mid:   capacity of each intermediate join result.
    out:   capacity of the final result shard.
    local: per-device resident-shard budget after placement.
    agg:   capacity of each pushed-down aggregate (cascade + pushdown).
    join:  capacity of the raw N-way join when the one-round plan must
           materialize it before aggregating (the paper's r''' term).
    """

    recv: int
    mid: int
    out: int
    local: Optional[int] = None
    agg: Optional[int] = None
    join: Optional[int] = None


def merge_stats(*stats: Stats) -> Stats:
    """Sum read/shuffled across rounds; ``max_bucket_load`` maxes."""
    out: Stats = {}
    for s in stats:
        for k, v in s.items():
            if k == "max_bucket_load":
                prev = out.get(k, jnp.zeros((), jnp.float32))
                out[k] = jnp.maximum(prev, v)
            elif k != "total":
                out[k] = out.get(k, jnp.zeros((), jnp.float32)) + v
    out["total"] = out.get("read", 0.0) + out.get("shuffled", 0.0)
    return out


def _count(grid: Grid, rel: Relation) -> jnp.ndarray:
    return grid.reduce_sum(grid.map_devices(lambda r: r.count(), rel))


def _hop_load(grid: Grid, rel: Relation, key: str, n_buckets: int,
              salt: int) -> jnp.ndarray:
    """Peak per-reducer load of one map-phase hop (skew diagnostic):
    the global bucket histogram of this hop's hash, via the Pallas
    kernel on TPU / jnp elsewhere."""
    hist = grid.map_devices(
        lambda r: bucket_counts(r.col(key), r.valid, n_buckets, salt=salt), rel)
    return jnp.max(grid.reduce_sum(hist)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# One-round Shares join on the join-attribute hypercube
# ---------------------------------------------------------------------------

_CLOSE = "_cc_"        # rename prefix for cycle-closing duplicate attrs


def _join_steps(query: JoinQuery, order: Sequence[int]):
    """Left-deep reduce-side plan along ``order`` — the query IR's
    :meth:`~repro.core.plan.JoinQuery.join_steps`, which the static
    verifier introspects so the plan it certifies is exactly the plan
    the executor runs."""
    return query.join_steps(order)


@jax.named_scope("join.emit")
def _close_cycle(acc: Relation, extras: Sequence[str]) -> Relation:
    """Apply the closing hop's extra equalities (`attr == _cc_attr`) and
    drop the renamed duplicates."""
    mask = jnp.ones(acc.valid.shape, jnp.bool_)
    for a in extras:
        mask = mask & (acc.col(a) == acc.col(_CLOSE + a))
    cols = {n: c for n, c in acc.cols.items()
            if n not in {_CLOSE + a for a in extras}}
    return Relation(cols, acc.valid & mask)


def place_relation(grid: Grid, query: JoinQuery, j: int, rel: Relation, *,
                   caps: ChainCaps, measure_skew: bool = False,
                   ) -> Tuple[Relation, jnp.ndarray, jnp.ndarray, Stats]:
    """The map/placement phase of one relation on the Shares hypercube:
    route to the pinned dims (one shuffle hop per hashed dim), replicate
    over the rest.  Returns (placed shard, overflow, peak bucket load,
    the live-row counter of the placement's buffers).

    This is the per-relation *lineage unit* of a one-round join: a
    placement that dies (a lost map task) is recovered by re-running
    exactly this function on the original input — which is what
    :func:`repro.resilience.recovery.resilient_one_round_query` does.
    """
    ndims = query.n_dims
    overflow = jnp.zeros((), jnp.bool_)
    skew = jnp.zeros((), jnp.float32)
    cur = rel
    fills = []
    hashed = query.hashed_dims(j)
    for d in hashed:                     # route to the pinned dims
        if grid.shape[d] == 1:
            continue                     # clamped dim: one bucket, no hop
        attr = query.dim_attr(d)
        if measure_skew:
            skew = jnp.maximum(
                skew, _hop_load(grid, cur, attr, grid.shape[d], salt=d))
        bucket = grid.map_devices(
            lambda r, _d=d, _a=attr: hashing.bucket_hash(
                r.col(_a), grid.shape[_d], salt=_d), cur)
        cur, ovf, fill = shuffle_by_bucket(grid, cur, bucket, d, caps.recv,
                                           local_capacity=caps.local)
        overflow = overflow | ovf
        fills.append(fill)
    for d in range(ndims):               # replicate over the rest
        if d in hashed or grid.shape[d] == 1:
            continue
        cur, ovf, fill = broadcast_along(grid, cur, d, caps.local)
        overflow = overflow | ovf
        fills.append(fill)
    return cur, overflow, skew, add_fill(*fills)


def reduce_side_fn(query: JoinQuery, order: Sequence[int], *,
                   caps: ChainCaps, join_impl: str = "sort_merge"):
    """Build the per-device reduce function of a one-round join: the
    left-deep chain of local joins along ``order``, cycle-closing
    filters applied at their hop.  Returns ``reduce(*shards) -> (acc,
    overflow, outputs)``, ``outputs`` every local join's output buffer
    (for the live-row counter) — pure per-device work, so it can be
    vmapped over the whole grid (the normal path) *or* run on one
    reducer coordinate's shards alone (the failed-bucket re-execution
    path of :func:`repro.resilience.recovery.resilient_one_round_query`)."""
    n = query.n_relations
    order = tuple(order)
    steps = _join_steps(query, order)
    out_caps = [caps.mid] * (n - 2) + [caps.join if (query.aggregate and
                                                     caps.join) else caps.out]

    def reduce_side(*shards: Relation):
        acc = shards[order[0]]
        ovf = jnp.zeros((), jnp.bool_)
        outs = []
        for i, (j, key, extras) in enumerate(steps):
            right = shards[j]
            if extras:
                right = right.rename({a: _CLOSE + a for a in extras})
            acc, o = local_join(acc, right, key, key, out_caps[i],
                                impl=join_impl)
            ovf = ovf | o
            if extras:
                acc = _close_cycle(acc, extras)
            outs.append(acc)
        return acc, ovf, tuple(outs)

    return reduce_side


def _reduce_split_fns(query: JoinQuery, order: Sequence[int], *,
                      caps: ChainCaps, join_impl: str = "sort_merge"):
    """:func:`reduce_side_fn` split at its last hop, for the overlapped
    one-round schedule: ``head`` runs the chain over every relation but
    ``order[-1]`` (computed once) and also returns its local joins'
    output buffers, ``tail(acc, shard)`` applies the final join +
    closing filters (run per placement chunk).  Returns
    ``(js_head, head, tail, final_cap)`` where ``js_head`` lists the
    relation indices ``head`` consumes, in ascending order."""
    n = query.n_relations
    order = tuple(order)
    steps = _join_steps(query, order)
    out_caps = [caps.mid] * (n - 2) + [caps.join if (query.aggregate and
                                                     caps.join) else caps.out]
    last = steps[-1][0]
    js_head = tuple(j for j in range(n) if j != last)

    def head(*shards: Relation):
        sh = dict(zip(js_head, shards))
        acc = sh[order[0]]
        ovf = jnp.zeros((), jnp.bool_)
        outs = []
        for i, (j, key, extras) in enumerate(steps[:-1]):
            right = sh[j]
            if extras:
                right = right.rename({a: _CLOSE + a for a in extras})
            acc, o = local_join(acc, right, key, key, out_caps[i],
                                impl=join_impl)
            ovf = ovf | o
            if extras:
                acc = _close_cycle(acc, extras)
            outs.append(acc)
        return acc, ovf, tuple(outs)

    _, key_l, extras_l = steps[-1]

    def tail(acc: Relation, shard: Relation):
        right = shard
        if extras_l:
            right = right.rename({a: _CLOSE + a for a in extras_l})
        out, o = local_join(acc, right, key_l, key_l, out_caps[-1],
                            impl=join_impl)
        if extras_l:
            out = _close_cycle(out, extras_l)
        return out, o

    return js_head, head, tail, out_caps[-1]


def one_round_query(grid: Grid, query: JoinQuery, rels: Sequence[Relation], *,
                    caps: ChainCaps, join_order: Optional[Sequence[int]] = None,
                    measure_skew: bool = False,
                    join_impl: str = "sort_merge",
                    overlap_chunks: int = 1,
                    ) -> Tuple[Relation, Stats, jnp.ndarray]:
    """One MapReduce round: place every relation on the join-attribute
    hypercube, then join locally.  Shuffled cost is Σ_j r_j · K /
    (∏ shares R_j pins) — the Shares communication charge for an
    arbitrary query hypergraph, measured exactly.

    The reduce side chains local joins along ``join_order`` (default:
    the query's greedy connected order); a hop whose relation shares
    several attributes with the running result equi-joins on the first
    and filters the rest — the cycle-closing predicates.  Tuples that
    agree on *all* their join attributes land on the same device (each
    relation is hashed on every join attribute it contains), so the
    per-device joins compose to the global result.

    ``overlap_chunks > 1`` selects the overlapped schedule: the last
    relation in the join order streams through placement in that many
    row chunks, each chunk's shuffle overlapping the previous chunk's
    final join (the head of the chain is computed once).  Tuple
    accounting, skew measurement, and the overflow condition are
    exactly the staged schedule's; only per-device output row order may
    differ."""
    n = query.n_relations
    query.check_relations(rels)
    ndims = query.n_dims
    if len(grid.shape) != ndims:
        raise ValueError(f"a {n}-relation query needs a rank-{ndims} grid, "
                         f"got shape {grid.shape}")

    read = sum(_count(grid, r) for r in rels)
    overflow = jnp.zeros((), jnp.bool_)
    skew = jnp.zeros((), jnp.float32)
    order = tuple(join_order) if join_order is not None \
        else query.default_join_order()

    fills: List[Stats] = []
    if overlap_chunks <= 1 or n < 2:
        placed: List[Relation] = []
        for j, rel in enumerate(rels):
            cur, ovf, sk, fill = place_relation(grid, query, j, rel,
                                                caps=caps,
                                                measure_skew=measure_skew)
            overflow = overflow | ovf
            skew = jnp.maximum(skew, sk)
            placed.append(cur)
            fills.append(fill)

        # Reduce side: left-deep chain of local joins (pure per-device
        # work).
        reduce_side = reduce_side_fn(query, order, caps=caps,
                                     join_impl=join_impl)
        joined, ovf_j, outs = grid.map_devices(reduce_side, *placed)
        overflow = overflow | jnp.any(grid.reduce_any(ovf_j))
        fills.append(buffer_fill(grid, *outs))

        # Measured shuffle = tuples resident at reducers after placement
        # (each relation counted with its replication factor).
        received = sum(_count(grid, p) for p in placed)
    else:
        # Overlapped schedule: place every relation but the last in the
        # join order, run the head chain once, then stream the last
        # relation through in row chunks — chunk b+1's placement
        # shuffle has no dependency on chunk b's join, so XLA overlaps
        # them.  The chunks partition the rows, so received counts,
        # skew histograms, and the overflow condition equal the staged
        # schedule's exactly; only per-device output row order differs.
        js_head, head, tail, final_cap = _reduce_split_fns(
            query, order, caps=caps, join_impl=join_impl)
        last = order[-1]
        placed_head: Dict[int, Relation] = {}
        for j in js_head:
            cur, ovf, sk, fill = place_relation(grid, query, j, rels[j],
                                                caps=caps,
                                                measure_skew=measure_skew)
            overflow = overflow | ovf
            skew = jnp.maximum(skew, sk)
            placed_head[j] = cur
            fills.append(fill)
        if measure_skew:
            # The last relation's hop histograms, measured on the full
            # input (identical to the staged measurement — chunk
            # histograms would each see a subset).
            for d in query.hashed_dims(last):
                if grid.shape[d] == 1:
                    continue
                skew = jnp.maximum(skew, _hop_load(
                    grid, rels[last], query.dim_attr(d), grid.shape[d],
                    salt=d))

        acc, ovf_h, outs = grid.map_devices(head, *[placed_head[j]
                                                    for j in js_head])
        overflow = overflow | jnp.any(grid.reduce_any(ovf_h))
        received = sum(_count(grid, p) for p in placed_head.values())
        fills.append(buffer_fill(grid, *outs))

        parts: List[Relation] = []
        for chunk in split_rows(rels[last], overlap_chunks):
            pc, ovf_c, _, fill = place_relation(grid, query, last, chunk,
                                                caps=caps, measure_skew=False)
            received = received + _count(grid, pc)
            out_c, ovf_t = grid.map_devices(tail, acc, pc)
            overflow = overflow | ovf_c | jnp.any(grid.reduce_any(ovf_t))
            parts.append(out_c)
            fills.append(fill)
        # Chunk matches are subsets of the staged hop's, so the chunk
        # joins at final_cap cannot overflow unless the staged join
        # would; the compaction reimposes the staged capacity and its
        # overflow condition.
        joined, ovf_cc = compact_to(grid, concat_rows(parts), final_cap)
        overflow = overflow | ovf_cc
        fills.append(buffer_fill(grid, *parts, joined))
    stats: Stats = {
        "read": read.astype(jnp.float32),
        "shuffled": received.astype(jnp.float32),
        **add_fill(*fills),
    }
    if measure_skew:
        stats["max_bucket_load"] = skew

    if query.aggregate is None:
        return joined, stats, overflow

    # 1,NJA: the raw join (size r''') must be shipped to the aggregator —
    # a charged round, the cost the pushdown cascade avoids.
    agg = query.aggregate
    join_cap = caps.join if caps.join else caps.out
    proj = project_product(grid, joined, keys=agg.keys,
                           value_cols=[v for v in query.values], out_name=agg.out)
    out, st_a, ovf_a = distributed_groupby_sum(
        grid, proj, keys=agg.keys, value=agg.out,
        recv_capacity=join_cap, out_capacity=caps.out,
        local_capacity=join_cap)
    return out, merge_stats(stats, st_a), overflow | ovf_a


def one_round_chain(grid: Grid, query: ChainQuery, rels: Sequence[Relation], *,
                    caps: ChainCaps, measure_skew: bool = False,
                    join_impl: str = "sort_merge",
                    overlap_chunks: int = 1,
                    ) -> Tuple[Relation, Stats, jnp.ndarray]:
    """The historical chain entry point — now the chain instance of
    :func:`one_round_query` (default join order ``0..N−1`` on the
    rank-(N−1) grid), bit-for-bit unchanged."""
    return one_round_query(grid, query, rels, caps=caps,
                           measure_skew=measure_skew, join_impl=join_impl,
                           overlap_chunks=overlap_chunks)


# ---------------------------------------------------------------------------
# Left-deep cascade: general queries (cycle-closing filters), then chains
# (with the paper's aggregation pushdown)
# ---------------------------------------------------------------------------

def cascade_query(grid: Grid, query: JoinQuery, rels: Sequence[Relation], *,
                  caps: ChainCaps, join_order: Optional[Sequence[int]] = None,
                  local_combine: bool = False,
                  measure_skew: bool = False,
                  join_impl: str = "sort_merge",
                  overlap_chunks: int = 1,
                  ) -> Tuple[Relation, Stats, jnp.ndarray]:
    """N−1 rounds of two-way joins along a connected left-deep
    ``join_order`` (default: the query's greedy order).

    ``overlap_chunks > 1`` runs every hop on the overlapped schedule —
    the incoming relation's shuffle streams in row chunks against the
    resident running intermediate (see :func:`~repro.core.two_way
    .two_way_join`) — with identical tuple accounting and overflow.

    Each round equi-joins the running intermediate with the next
    relation on their first shared attribute across the whole grid; any
    further shared attributes — the cycle-closing predicates — are
    applied as per-device post-join filters at that hop, so only tuples
    satisfying the closing equalities ship onward.  Aggregated queries
    run one final *charged* aggregation round (general queries have no
    sound intermediate pushdown; chains should use
    :func:`cascade_chain`, which pushes the aggregation down between
    rounds).

    Cost accounting is the paper's: each round charges read + shuffled
    on both inputs, so the measured total equals
    :func:`repro.core.cost_model.cost_query_cascade` over the order's
    post-filter intermediate sizes, exactly.
    """
    n = query.n_relations
    query.check_relations(rels)
    agg = query.aggregate
    order = tuple(join_order) if join_order is not None \
        else query.default_join_order()
    steps = _join_steps(query, order)

    k_flat = 1
    for s in grid.shape:
        k_flat *= s

    all_stats: List[Stats] = []
    overflow = jnp.zeros((), jnp.bool_)
    skew = jnp.zeros((), jnp.float32)

    left = rels[order[0]]
    left_cap = None                       # None => first round uses caps.recv
    value_cols: List[str] = \
        [query.values[order[0]]] if query.values[order[0]] else []

    for i, (j, key, extras) in enumerate(steps):
        right = rels[j]
        if extras:
            right = right.rename({a: _CLOSE + a for a in extras})
        recv = caps.recv if left_cap is None else max(left_cap, caps.recv)
        local = caps.local if left_cap is None else max(left_cap, caps.recv)
        out_cap = caps.out if i == n - 2 else caps.mid
        if measure_skew:
            skew = jnp.maximum(skew, _hop_load(grid, left, key, k_flat,
                                               salt=i))
            skew = jnp.maximum(skew, _hop_load(grid, right, key, k_flat,
                                               salt=i))
        left, st, ovf = two_way_join(
            grid, left, right, key, key,
            recv_capacity=recv, out_capacity=out_cap,
            local_capacity=local, salt=i, join_impl=join_impl,
            overlap_chunks=overlap_chunks)
        if extras:
            left = grid.map_devices(
                lambda r, _e=extras: _close_cycle(r, _e), left)
        all_stats.append(st)
        overflow = overflow | ovf
        left_cap = out_cap
        if query.values[j]:
            value_cols.append(query.values[j])

    if agg is not None:
        # Final Γ_{keys; SUM ∏ values} — a charged aggregation round
        # (the raw result ships to the aggregators: the 2·|result| term).
        proj = project_product(grid, left, keys=tuple(agg.keys),
                               value_cols=value_cols, out_name=agg.out)
        fin_cap = caps.out
        left, st_f, ovf_f = distributed_groupby_sum(
            grid, proj, keys=tuple(agg.keys), value=agg.out,
            recv_capacity=fin_cap, out_capacity=fin_cap,
            local_capacity=fin_cap, local_combine=local_combine)
        overflow = overflow | ovf_f
        all_stats.append(st_f)

    stats = merge_stats(*all_stats)
    if measure_skew:
        stats["max_bucket_load"] = skew
    return left, stats, overflow

def cascade_chain(grid: Grid, query: ChainQuery, rels: Sequence[Relation], *,
                  caps: ChainCaps, pushdown: bool = True,
                  local_combine: bool = False,
                  include_final_agg: bool = False,
                  measure_skew: bool = False,
                  join_impl: str = "sort_merge",
                  overlap_chunks: int = 1,
                  ) -> Tuple[Relation, Stats, jnp.ndarray]:
    """N−1 rounds of two-way joins, left-deep in query order.

    With an aggregation and ``pushdown=True``, every non-final round is
    followed by Γ_{A_1, A_{j+2}; SUM} of the running value product —
    the paper's 2,3JA generalized (intermediates shrink to the
    aggregated size before the next shuffle).  Without pushdown the
    aggregation runs once at the end and is charged (the 1,3JA
    convention applied to the cascade).
    """
    n = query.n_relations
    query.check_relations(rels)
    agg = query.aggregate
    if agg is None:
        pushdown = False

    k_flat = 1
    for s in grid.shape:
        k_flat *= s

    all_stats: List[Stats] = []
    overflow = jnp.zeros((), jnp.bool_)
    skew = jnp.zeros((), jnp.float32)

    left = rels[0]
    left_cap = None                       # None => first round uses caps.recv
    value_cols: List[str] = [query.values[0]] if query.values[0] else []

    for j in range(1, n):
        key = query.attrs[j]
        recv = caps.recv if left_cap is None else max(left_cap, caps.recv)
        local = caps.local if left_cap is None else max(left_cap, caps.recv)
        out_cap = caps.out if j == n - 1 else caps.mid
        if measure_skew:
            skew = jnp.maximum(skew, _hop_load(grid, left, key, k_flat,
                                               salt=j - 1))
            skew = jnp.maximum(skew, _hop_load(grid, rels[j], key, k_flat,
                                               salt=j - 1))
        left, st, ovf = two_way_join(
            grid, left, rels[j], key, key,
            recv_capacity=recv, out_capacity=out_cap,
            local_capacity=local, salt=j - 1, join_impl=join_impl,
            overlap_chunks=overlap_chunks)
        all_stats.append(st)
        overflow = overflow | ovf
        left_cap = out_cap
        if query.values[j]:
            value_cols.append(query.values[j])

        if pushdown and j < n - 1:
            # Γ_{A_1, A_{j+2}; SUM prod} — the pushdown round (charged).
            keys = (query.attrs[0], query.attrs[j + 1])
            proj = project_product(grid, left, keys=keys,
                                   value_cols=value_cols, out_name=agg.out)
            agg_cap = caps.agg if caps.agg else caps.mid
            left, st_a, ovf_a = distributed_groupby_sum(
                grid, proj, keys=keys, value=agg.out,
                recv_capacity=left_cap, out_capacity=agg_cap,
                local_capacity=left_cap, local_combine=local_combine)
            all_stats.append(st_a)
            overflow = overflow | ovf_a
            left_cap = agg_cap
            value_cols = [agg.out]

    if agg is not None:
        # Final Γ_{A_1, A_{N+1}; SUM}.  Under pushdown this matches the
        # paper's uncharged final aggregator (formula 6r+2r'+2r'');
        # without pushdown it is the (charged) aggregation round.
        proj = project_product(grid, left, keys=tuple(agg.keys),
                               value_cols=value_cols, out_name=agg.out)
        fin_cap = caps.out
        left, st_f, ovf_f = distributed_groupby_sum(
            grid, proj, keys=tuple(agg.keys), value=agg.out,
            recv_capacity=fin_cap, out_capacity=fin_cap,
            local_capacity=fin_cap, local_combine=local_combine)
        overflow = overflow | ovf_f
        if include_final_agg or not pushdown:
            all_stats.append(st_f)
        else:                   # uncharged, but its buffers are filled
            all_stats.append({k: st_f[k] for k in FILL_KEYS})

    stats = merge_stats(*all_stats)
    if measure_skew:
        stats["max_bucket_load"] = skew
    return left, stats, overflow


# ---------------------------------------------------------------------------
# Map-side cascade: merge-join stored partitions, shuffle only when unproven
# ---------------------------------------------------------------------------

def _device_layout(rel) -> Tuple[Relation, bool]:
    """Per-device form of a cascade input: a
    :class:`~repro.core.partition.PartitionedRelation`'s ``parts`` ARE
    its placement (partition p lives on device p) and are known sorted;
    a plain grid-scattered :class:`Relation` is used as-is, unsorted."""
    from .partition import PartitionedRelation
    if isinstance(rel, PartitionedRelation):
        return rel.parts, rel.spec.sorted
    return rel, False


def mapside_cascade_chain(grid: Grid, query: ChainQuery, rels, *,
                          caps: ChainCaps, partitioning, hop_modes,
                          place_output: bool = False,
                          measure_skew: bool = False,
                          join_impl: str = "sort_merge",
                          overlap_chunks: int = 1,
                          ) -> Tuple[Relation, Stats, jnp.ndarray]:
    """The zero-shuffle cascade over the partitioned store (MS,NJ[A]).

    ``rels`` mixes :class:`~repro.core.partition.PartitionedRelation`
    (stored hash-partitioned + key-sorted — its ``parts`` feed the grid
    with no placement hop) and grid-scattered plain :class:`Relation`
    inputs, in query order.  ``partitioning`` is the
    :class:`~repro.core.cost_model.ChainPartitioning` certificate and
    ``hop_modes`` the planner's per-hop choice
    (:func:`~repro.core.cost_model.chain_mapside_modes`):

    * ``"mapside"`` — relation j is proven co-partitioned on the hop
      key: the running intermediate repartitions by the *stored* hash
      (``bucket_hash(key, P, salt)``) onto the partition grid — or
      moves nothing at all on hop 1 when relation 0 is pre-partitioned
      on the first join key (``left0_proven``) — and every device
      merge-joins against its resident partition with the sort skipped
      on the stored side (``presorted_r``).  The stored relation ships
      **zero tuples**.
    * ``"broadcast"`` — relation j replicates to all P devices
      (charged P·|r_j|); the intermediate does not move.
    * ``"shuffle"`` — the ordinary :func:`two_way_join` hop (both sides
      hash-shuffle).

    With ``place_output`` each hop's result is repartitioned onto the
    *next* hop's join key immediately — whenever the next hop is proven
    — so the cascade's intermediates land already partitioned where the
    next stored relation lives and every proven hop shuffles exactly
    zero tuples.  The movement is reported as ``"placed"`` /
    ``"hop_placed"`` (charged into ``total``): shuffled + placed
    together move each tuple at most once, and their sum is identical
    with or without placement — placement only re-times the move.

    Runs on the 1-D partition grid (``grid.shape == (P,)``).  Stats are
    the uniform convention — read + shuffled per hop, measured — plus
    ``"hop_shuffled"``: the per-hop shuffled-tuple vector the map-side
    benchmark pins against the analytic
    :func:`~repro.core.cost_model.chain_mapside_shuffles` (and
    ``"hop_placed"`` against
    :func:`~repro.core.cost_model.chain_mapside_placed`).  Aggregated
    queries run one final charged Γ round (no pushdown on this path —
    re-keying the intermediate would destroy nothing, but the paper's
    pushdown charge model assumes shuffled intermediates, so the plain
    convention keeps measured == analytic).
    """
    n = query.n_relations
    P = partitioning.num_partitions
    if len(grid.shape) != 1 or grid.shape[0] != P:
        raise ValueError(f"map-side cascade needs the 1-D partition grid "
                         f"({P},), got {grid.shape}")
    if len(hop_modes) != n - 1:
        raise ValueError(f"{n - 1} hops need {n - 1} modes, got "
                         f"{len(hop_modes)}")
    for j, mode in enumerate(hop_modes):
        if mode == "mapside" and not partitioning.right_proven[j]:
            raise ValueError(f"hop {j + 1} is not proven co-partitioned; "
                             f"mode 'mapside' would be unsound")
    if (partitioning.key_dtype is not None
            and partitioning.key_dtype != config.key_dtype_name()):
        raise ValueError(
            f"partitioning certificate was minted over "
            f"{partitioning.key_dtype} keys but the current configuration "
            f"uses {config.key_dtype_name()}; the partition hash folds "
            f"64-bit keys, so the stored layout proves nothing here — "
            f"repartition under the current dtype")

    all_stats: List[Stats] = []
    hop_shuffled: List[jnp.ndarray] = []
    hop_placed: List[jnp.ndarray] = []
    overflow = jnp.zeros((), jnp.bool_)
    skew = jnp.zeros((), jnp.float32)
    zero = jnp.zeros((), jnp.float32)

    left, left_sorted = _device_layout(rels[0])
    left_on_key = bool(partitioning.left0_proven)
    left_cap = None                       # None => first hop uses caps.recv
    value_cols: List[str] = [query.values[0]] if query.values[0] else []

    for j in range(1, n):
        key = query.attrs[j]
        mode = hop_modes[j - 1]
        right, right_sorted = _device_layout(rels[j])
        recv = caps.recv if left_cap is None else max(left_cap, caps.recv)
        local = caps.local if left_cap is None else max(left_cap, caps.recv)
        out_cap = caps.out if j == n - 1 else caps.mid

        if mode == "shuffle":
            if measure_skew:
                skew = jnp.maximum(skew, _hop_load(grid, left, key, P,
                                                   salt=j - 1))
                skew = jnp.maximum(skew, _hop_load(grid, right, key, P,
                                                   salt=j - 1))
            left, st, ovf = two_way_join(
                grid, left, right, key, key,
                recv_capacity=recv, out_capacity=out_cap,
                local_capacity=local, salt=j - 1, join_impl=join_impl,
                overlap_chunks=overlap_chunks)
            all_stats.append(st)
            hop_shuffled.append(st["shuffled"])
            overflow = overflow | ovf
        else:
            read = (_count(grid, left) + _count(grid, right)
                    ).astype(jnp.float32)
            fill = buffer_fill(grid)
            if mode == "broadcast":
                right, ovf_b, fill = broadcast_along(grid, right, 0, local)
                overflow = overflow | ovf_b
                shuffled = _count(grid, right).astype(jnp.float32)
                pre_l, pre_r = False, False   # the gather interleaves runs
            else:                             # mapside
                if left_on_key:
                    shuffled = zero           # both sides already in place
                    pre_l = left_sorted
                else:
                    if measure_skew:
                        skew = jnp.maximum(skew, _hop_load(
                            grid, left, key, P, salt=partitioning.salt))
                    bucket = grid.map_devices(
                        lambda r, _a=key: hashing.bucket_hash(
                            r.col(_a), P, salt=partitioning.salt), left)
                    left, ovf_s, fill = shuffle_by_bucket(
                        grid, left, bucket, 0, recv, local_capacity=local)
                    overflow = overflow | ovf_s
                    shuffled = _count(grid, left).astype(jnp.float32)
                    pre_l = False
                pre_r = right_sorted

            def hop(l, r, _k=key, _c=out_cap, _pl=pre_l, _pr=pre_r):
                return local_join(l, r, _k, _k, _c, impl=join_impl,
                                  presorted_l=_pl, presorted_r=_pr)

            left, ovf_j = grid.map_devices(hop, left, right)
            overflow = overflow | jnp.any(grid.reduce_any(ovf_j))
            all_stats.append({"read": read, "shuffled": shuffled,
                              **add_fill(fill, buffer_fill(grid, left))})
            hop_shuffled.append(shuffled)

        left_sorted = False
        left_on_key = False
        if place_output and j < n - 1 and hop_modes[j] == "mapside":
            # Land the intermediate already partitioned on the next
            # hop's key (the stored hash) — its one move, made at birth.
            next_key = query.attrs[j + 1]
            bucket = grid.map_devices(
                lambda r, _a=next_key: hashing.bucket_hash(
                    r.col(_a), P, salt=partitioning.salt), left)
            # Per-(dest, source) slots carry ~1/P of a device's share, so
            # the same slack fits in out_cap/P-sized slots — placement
            # buffers stay a fraction of a shuffle hop's.
            slot = -(-out_cap // P) + 256
            left, ovf_p, fill = shuffle_by_bucket(grid, left, bucket, 0,
                                                  slot,
                                                  local_capacity=out_cap)
            overflow = overflow | ovf_p
            all_stats.append(fill)
            hop_placed.append(_count(grid, left).astype(jnp.float32))
            left_on_key = True
        else:
            hop_placed.append(zero)
        left_cap = out_cap
        if query.values[j]:
            value_cols.append(query.values[j])

    if query.aggregate is not None:
        agg = query.aggregate
        proj = project_product(grid, left, keys=tuple(agg.keys),
                               value_cols=value_cols, out_name=agg.out)
        fin_cap = caps.out
        left, st_f, ovf_f = distributed_groupby_sum(
            grid, proj, keys=tuple(agg.keys), value=agg.out,
            recv_capacity=fin_cap, out_capacity=fin_cap,
            local_capacity=fin_cap)
        overflow = overflow | ovf_f
        all_stats.append(st_f)

    stats = merge_stats(*all_stats)
    stats["hop_shuffled"] = jnp.stack(hop_shuffled)
    stats["hop_placed"] = jnp.stack(hop_placed)
    stats["placed"] = sum(hop_placed, zero)
    stats["total"] = stats["total"] + stats["placed"]
    if measure_skew:
        stats["max_bucket_load"] = skew
    return left, stats, overflow


# ---------------------------------------------------------------------------
# SkewSplit lowering: the SharesSkew union of per-combination sub-joins
# ---------------------------------------------------------------------------

def _heavy_member(col: jnp.ndarray, heavy) -> jnp.ndarray:
    """Membership of a key column in a (small, host-side) heavy set."""
    import numpy as np
    heavy = np.asarray(heavy)
    if heavy.size == 0:
        return jnp.zeros(col.shape, jnp.bool_)
    # Compare in the column's own dtype: an int32 cast here would
    # truncate int64 heavy keys and misclassify their tuples.
    hv = jnp.asarray(heavy).astype(col.dtype)  # lint: allow-key-cast
    return jnp.any(col[:, None] == hv[None, :], axis=1)


def _combo_filter(query: ChainQuery, plan, combo, j: int,
                  rel: Relation) -> Relation:
    """Relation j's part for one combination: keep a tuple iff, for each
    of the relation's own join attributes, its heavy/residual status
    matches the combination's choice for that dim."""
    mask = jnp.ones(rel.valid.shape, jnp.bool_)
    for d in query.hashed_dims(j):
        member = _heavy_member(rel.col(query.dim_attr(d)), plan.heavy[d])
        mask = mask & (member if combo.heavy_dims[d] else ~member)
    return rel.filter(mask)


def _flatten_grid(rel: Relation, grid_rank: int) -> Relation:
    """Collapse the leading grid axes into one flat buffer."""
    cols = {n: c.reshape((-1,) + c.shape[grid_rank + 1:])
            for n, c in rel.cols.items()}
    return Relation(cols, rel.valid.reshape(-1))


def shares_skew_chain(query: ChainQuery, rels: Sequence[Relation], plan, *,
                      caps, measure_skew: bool = False,
                      join_impl: str = "sort_merge",
                      overlap_chunks: int = 1,
                      ) -> Tuple[Relation, Stats, jnp.ndarray]:
    """SkewSplit lowering (SharesSkew): one Shares sub-join per
    heavy/residual combination, unioned.

    ``rels`` are *flat* (host-layout, unscattered) relations in query
    order; ``plan`` is a :class:`repro.core.skew.SkewSplitPlan`.  Each
    combination filters every relation to its part, scatters the parts
    onto the combination's grid (the plain integer-share hypercube with
    heavy dims clamped to share 1 — heavy tuples broadcast there, the
    ``broadcast_along`` of the clamped dim being a no-op of size 1 means
    they are simply replicated over the surviving dims), and runs
    :func:`one_round_chain`.  ``caps`` is a :class:`ChainCaps` used for
    every combination, or a callable ``combo -> ChainCaps``.

    Join results union disjointly across combinations (every output
    tuple has a definite heavy/residual status per join attribute); for
    aggregated queries the per-combination partial sums are merged by a
    final local group-by, uncharged like the paper's final aggregator.
    Stats sum across combinations (``max_bucket_load`` maxes), so the
    measured total equals ``plan.cost()`` exactly for enumeration
    queries, and ``plan.cost() + 2·|full join|`` for aggregated ones
    (each combination charges its own aggregation round, and the
    combinations partition the join output).  Each combination is its
    own round, so a relation pinning only clamped dims is re-read by
    every combination that keeps its tuples — the same convention the
    analytic cost charges.

    A plan with *no* combinations means every combination had an empty
    input part, which proves the join itself is empty: the result is an
    empty relation at zero cost.
    """
    query.check_relations(rels)
    if not plan.combos:
        zero = jnp.zeros((), jnp.float32)
        stats: Stats = {"read": zero, "shuffled": zero, "total": zero,
                        **{k: zero for k in FILL_KEYS}}
        if measure_skew:
            stats["max_bucket_load"] = zero
        # Key dtypes come from the actual input columns so an empty
        # result under x64 still carries int64 keys.
        key_dt: dict = {}
        for j, rel in enumerate(rels):
            for a in query.relations[j]:
                key_dt.setdefault(a, rel.col(a).dtype)
        if query.aggregate is not None:
            schema = {k: key_dt.get(k, config.default_key_dtype())
                      for k in query.aggregate.keys}
            schema[query.aggregate.out] = jnp.float32
        else:
            schema = {a: key_dt.get(a, config.default_key_dtype())
                      for a in query.attrs}
            for j, v in enumerate(query.values):
                if v is not None:
                    schema[v] = rels[j].col(v).dtype
        return (Relation.empty(1, schema), stats,
                jnp.zeros((), jnp.bool_))
    n = query.n_relations
    all_stats: List[Stats] = []
    parts: List[Relation] = []
    overflow = jnp.zeros((), jnp.bool_)
    for combo in plan.combos:
        sub = [scatter_to_grid(_combo_filter(query, plan, combo, j, rel),
                               combo.grid_shape)
               for j, rel in enumerate(rels)]
        grid = SimGrid(combo.grid_shape)
        combo_caps = caps(combo) if callable(caps) else caps
        out, st, ovf = one_round_chain(grid, query, sub, caps=combo_caps,
                                       measure_skew=measure_skew,
                                       join_impl=join_impl,
                                       overlap_chunks=overlap_chunks)
        parts.append(_flatten_grid(out, n - 1))
        all_stats.append(st)
        overflow = overflow | ovf

    result = concat(parts)
    if query.aggregate is not None:
        agg = query.aggregate
        result, ovf_m = groupby_sum(result, tuple(agg.keys), agg.out)
        overflow = overflow | ovf_m
        all_stats.append(buffer_fill(SimGrid(()), result))
    return result, merge_stats(*all_stats), overflow


# ---------------------------------------------------------------------------
# Entry point: run a logical plan
# ---------------------------------------------------------------------------

def execute_chain(grid: Grid, query: ChainQuery, rels: Sequence[Relation], *,
                  strategy: str, caps: ChainCaps,
                  measure_skew: bool = False, local_combine: bool = False,
                  include_final_agg: bool = False,
                  join_impl: str = "sort_merge",
                  partitioning=None, hop_modes=None,
                  place_output: bool = False,
                  overlap_chunks: int = 1,
                  ) -> Tuple[Relation, Stats, jnp.ndarray]:
    """Execute ``query`` with a planner-chosen strategy:

    * ``"one_round"``          — Shares hypercube (1,NJ / 1,NJA)
    * ``"cascade"``            — plain left-deep cascade (N−1,NJ)
    * ``"cascade_pushdown"``   — cascade with aggregation pushdown (N−1,NJA)
    * ``"mapside"``            — merge-join the partitioned store (MS,NJ[A]);
      needs ``partitioning`` (the
      :class:`~repro.core.cost_model.ChainPartitioning` certificate) and
      ``hop_modes`` from the :class:`~repro.core.planner.ChainPlan`, a
      1-D grid of ``num_partitions`` devices, and ``rels`` entries that
      are :class:`~repro.core.partition.PartitionedRelation` on every
      proven position (:func:`mapside_cascade_chain`).

    ``join_impl`` selects the reduce-side join kernel for every
    strategy: ``"sort_merge"`` (default), ``"fused"`` (the rank-packed
    pipeline), or the ``"all_pairs"`` oracle — identical tuple sets,
    stats, and overflow flags (see docs/architecture.md "Data plane").
    ``overlap_chunks > 1`` selects the overlapped shuffle schedule on
    every strategy (see docs/overlap.md) — identical accounting, only
    per-device output row order may differ.

    The skew-aware strategy ``"shares_skew"`` (1,NJS) cannot run on a
    single pre-scattered grid — its sub-joins each use their own clamped
    grid — so it has its own entry point, :func:`shares_skew_chain`,
    taking flat relations plus a ``SkewSplitPlan``.
    """
    if strategy == "mapside":
        if partitioning is None or hop_modes is None:
            raise ValueError("mapside needs partitioning and hop_modes "
                             "(plan with plan_chain(partitioning=...))")
        return mapside_cascade_chain(grid, query, rels, caps=caps,
                                     partitioning=partitioning,
                                     hop_modes=hop_modes,
                                     place_output=place_output,
                                     measure_skew=measure_skew,
                                     join_impl=join_impl,
                                     overlap_chunks=overlap_chunks)
    if strategy == "shares_skew":
        raise ValueError(
            "shares_skew runs per-combination grids; call "
            "shares_skew_chain(query, flat_rels, plan, caps=...) with the "
            "SkewSplitPlan from repro.core.skew.detect_chain_skew")
    if strategy == "one_round":
        return one_round_chain(grid, query, rels, caps=caps,
                               measure_skew=measure_skew,
                               join_impl=join_impl,
                               overlap_chunks=overlap_chunks)
    if strategy == "cascade":
        return cascade_chain(grid, query, rels, caps=caps, pushdown=False,
                             measure_skew=measure_skew,
                             local_combine=local_combine,
                             join_impl=join_impl,
                             overlap_chunks=overlap_chunks)
    if strategy == "cascade_pushdown":
        if query.aggregate is None:
            raise ValueError("cascade_pushdown needs an aggregated query")
        return cascade_chain(grid, query, rels, caps=caps, pushdown=True,
                             measure_skew=measure_skew,
                             local_combine=local_combine,
                             include_final_agg=include_final_agg,
                             join_impl=join_impl,
                             overlap_chunks=overlap_chunks)
    raise ValueError(f"unknown strategy {strategy!r}")


def execute_query(grid: Grid, query: JoinQuery, rels: Sequence[Relation], *,
                  strategy: str, caps: ChainCaps,
                  join_order: Optional[Sequence[int]] = None,
                  measure_skew: bool = False, local_combine: bool = False,
                  include_final_agg: bool = False,
                  join_impl: str = "sort_merge",
                  overlap_chunks: int = 1,
                  ) -> Tuple[Relation, Stats, jnp.ndarray]:
    """Execute a general :class:`JoinQuery` — chain, cycle, star, or any
    connected hypergraph — with a planner-chosen strategy:

    * ``"one_round"``        — Shares hypercube, one dim per join
      attribute (:func:`one_round_query`);
    * ``"cascade"``          — left-deep two-way rounds along
      ``join_order``, cycle-closing predicates filtering at their hop
      (:func:`cascade_query`); aggregated queries add a charged final
      aggregation round;
    * ``"cascade_pushdown"`` — the chain-only pushdown cascade
      (:func:`cascade_chain`); requires the query hypergraph to be a
      chain in relation order (``chain_attr_order()``), since pushing
      Γ between rounds is only sound for endpoint aggregates.

    ``join_order`` defaults to the query's greedy connected order; the
    planner's :class:`~repro.core.planner.QueryPlan` carries the
    cost-chosen one.  ``join_impl`` selects the reduce-side kernel as
    everywhere else.  The skew-aware ``"shares_skew"`` strategy stays
    chain-only — see :func:`shares_skew_chain`.
    """
    if strategy == "one_round":
        return one_round_query(grid, query, rels, caps=caps,
                               join_order=join_order,
                               measure_skew=measure_skew,
                               join_impl=join_impl,
                               overlap_chunks=overlap_chunks)
    if strategy == "cascade":
        return cascade_query(grid, query, rels, caps=caps,
                             join_order=join_order,
                             measure_skew=measure_skew,
                             local_combine=local_combine,
                             join_impl=join_impl,
                             overlap_chunks=overlap_chunks)
    if strategy == "cascade_pushdown":
        order = query.chain_attr_order()
        if query.aggregate is None or order is None or order != query.attrs:
            raise ValueError("cascade_pushdown needs an aggregated chain "
                             "query (pushdown between rounds is only sound "
                             "for endpoint aggregates on a chain)")
        return cascade_chain(grid, query, rels, caps=caps, pushdown=True,
                             measure_skew=measure_skew,
                             local_combine=local_combine,
                             include_final_agg=include_final_agg,
                             join_impl=join_impl,
                             overlap_chunks=overlap_chunks)
    if strategy == "shares_skew":
        raise ValueError(
            "shares_skew runs per-combination grids and is chain-only; call "
            "shares_skew_chain(query, flat_rels, plan, caps=...) with the "
            "SkewSplitPlan from repro.core.skew.detect_chain_skew")
    raise ValueError(f"unknown strategy {strategy!r}")


# ---------------------------------------------------------------------------
# Whole-plan compilation: one XLA program per (plan, caps)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _compiled_sim_chain(grid_shape: Tuple[int, ...], query: ChainQuery,
                        strategy: str, caps: ChainCaps, opts: Tuple,
                        donate: bool):
    return _jit_chain(SimGrid(grid_shape), query, strategy, caps, opts,
                      donate)


@functools.lru_cache(maxsize=32)
def _compiled_grid_chain(grid: Grid, query: ChainQuery, strategy: str,
                         caps: ChainCaps, opts: Tuple, donate: bool):
    # Non-Sim grids hash by identity: the cache holds per-instance
    # programs (the realistic usage — one long-lived ShardGrid).
    return _jit_chain(grid, query, strategy, caps, opts, donate)


def _jit_chain(grid: Grid, query: ChainQuery, strategy: str, caps: ChainCaps,
               opts: Tuple, donate: bool):
    def run(rels):
        return execute_chain(grid, query, list(rels), strategy=strategy,
                             caps=caps, **dict(opts))

    return jax.jit(run, donate_argnums=(0,) if donate else ())


def jit_execute_chain(grid: Grid, query: ChainQuery, *, strategy: str,
                      caps: ChainCaps, donate: bool = True, **opts):
    """Compile the *entire* chain-query execution into one XLA program.

    Returns ``run(rels) -> (Relation, Stats, overflow)`` — the whole
    lowering (every shuffle hop, local join, and aggregation round)
    traced once and jitted as a unit, instead of dispatching each hop's
    ops eagerly.  Because every buffer is static-shape, the program is
    reusable for any inputs of the same capacities.  Programs are
    cached so repeated calls with the same plan skip retracing: for
    :class:`SimGrid` the key is (grid *shape*, query, strategy, caps,
    options) — any equal-shaped SimGrid hits; for other grids the key
    uses the grid *instance*, so reuse requires passing the same grid
    object (constructing a fresh ShardGrid per call would recompile).

    ``donate=True`` donates the input relation buffers to the computation
    (XLA may reuse them for outputs — they must not be read afterwards;
    backends without donation support, e.g. CPU, ignore it with a
    warning).  Options (``measure_skew``, ``local_combine``,
    ``include_final_agg``, ``join_impl``) forward to
    :func:`execute_chain`.
    """
    opts_key = tuple(sorted(opts.items()))
    if isinstance(grid, SimGrid):
        return _compiled_sim_chain(grid.shape, query, strategy, caps,
                                   opts_key, donate)
    return _compiled_grid_chain(grid, query, strategy, caps, opts_key, donate)


@functools.lru_cache(maxsize=128)
def _compiled_sim_query(grid_shape: Tuple[int, ...], query: JoinQuery,
                        strategy: str, caps: ChainCaps, opts: Tuple,
                        donate: bool):
    return _jit_query(SimGrid(grid_shape), query, strategy, caps, opts,
                      donate)


@functools.lru_cache(maxsize=32)
def _compiled_grid_query(grid: Grid, query: JoinQuery, strategy: str,
                         caps: ChainCaps, opts: Tuple, donate: bool):
    return _jit_query(grid, query, strategy, caps, opts, donate)


def _jit_query(grid: Grid, query: JoinQuery, strategy: str, caps: ChainCaps,
               opts: Tuple, donate: bool):
    def run(rels):
        return execute_query(grid, query, list(rels), strategy=strategy,
                             caps=caps, **dict(opts))

    return jax.jit(run, donate_argnums=(0,) if donate else ())


def clear_compiled_caches() -> None:
    """Drop every cached whole-plan executable
    (:func:`jit_execute_chain` / :func:`jit_execute_query`).  The
    serving benchmark uses this to measure a genuinely cold
    plan+compile against the warm cache-hit path; production code
    never needs it."""
    _compiled_sim_chain.cache_clear()
    _compiled_grid_chain.cache_clear()
    _compiled_sim_query.cache_clear()
    _compiled_grid_query.cache_clear()


def jit_execute_query(grid: Grid, query: JoinQuery, *, strategy: str,
                      caps: ChainCaps, donate: bool = True, **opts):
    """Compile an *entire* general-query execution into one XLA program
    — :func:`jit_execute_chain` lifted to :class:`JoinQuery` (same
    caching, donation, and reuse semantics).  Options (``join_order``,
    ``measure_skew``, ``local_combine``, ``include_final_agg``,
    ``join_impl``) forward to :func:`execute_query`; a ``join_order``
    list must be passed as a tuple (the cache key hashes it)."""
    opts_key = tuple(sorted(opts.items()))
    if isinstance(grid, SimGrid):
        return _compiled_sim_query(grid.shape, query, strategy, caps,
                                   opts_key, donate)
    return _compiled_grid_query(grid, query, strategy, caps, opts_key, donate)


# ---------------------------------------------------------------------------
# Driver helpers: input placement and capacity sizing
# ---------------------------------------------------------------------------

def scatter_to_grid(rel: Relation, grid_shape: Sequence[int]) -> Relation:
    """Round-robin a host relation over grid devices (mapper placement):
    every column reshapes to (*grid_shape, rows_per_device)."""
    shape = tuple(grid_shape)
    n_dev = 1
    for s in shape:
        n_dev *= s
    per = -(-rel.capacity // n_dev)
    pad = per * n_dev - rel.capacity
    cols = {k: jnp.pad(c, (0, pad)).reshape(shape + (per,))
            for k, c in rel.cols.items()}
    valid = jnp.pad(rel.valid, (0, pad)).reshape(shape + (per,))
    return Relation(cols, valid)


def chain_edge_inputs(query: ChainQuery, edge_lists,
                      grid_shape: Sequence[int]) -> List[Relation]:
    """Edge lists -> scattered per-relation inputs named by the query
    schema (requires a value column on every relation)."""
    from .matmul import edge_relation  # local import: matmul uses the wrappers
    rels = []
    for j, (src, dst) in enumerate(edge_lists):
        a, b, v = query.schema(j)
        rels.append(scatter_to_grid(
            edge_relation(src, dst, names=(a, b, v)), grid_shape))
    return rels


def query_table_inputs(query: JoinQuery, tables,
                       grid_shape: Sequence[int],
                       key_dtype=None) -> List[Relation]:
    """Column tables -> scattered per-relation inputs named by the query
    schema.  ``tables[j]`` is a tuple of equal-length key column arrays
    matching relation j's attribute tuple; a trailing value column may
    be included, otherwise a ones value column is synthesized when the
    schema asks for one (so edge lists ``(src, dst)`` work for any
    binary relation — the general counterpart of
    :func:`chain_edge_inputs`).  ``key_dtype`` defaults to the
    configured key dtype — int64 under x64 mode, else int32 (see
    ``repro.config.default_key_dtype``)."""
    key_dtype = config.default_key_dtype() if key_dtype is None else key_dtype
    rels = []
    for j, cols in enumerate(tables):
        names = query.schema(j)
        arity = len(query.relations[j])
        if len(cols) not in (arity, len(names)):
            raise ValueError(f"relation {j} needs {arity} key columns "
                             f"(+ optional value), got {len(cols)}")
        arrays = {names[i]: jnp.asarray(c, key_dtype)
                  for i, c in enumerate(cols[:arity])}
        if query.values[j] is not None:
            val = (jnp.asarray(cols[arity], jnp.float32)
                   if len(cols) > arity
                   else jnp.ones_like(arrays[names[0]], dtype=jnp.float32))
            arrays[query.values[j]] = val
        rels.append(scatter_to_grid(Relation.from_arrays(**arrays),
                                    grid_shape))
    return rels


def default_query_caps(query: JoinQuery, stats, grid_shape: Sequence[int],
                       slack: int = 6) -> ChainCaps:
    """Size ChainCaps for a general query from exact
    :class:`~repro.core.cost_model.QueryStats`: every buffer gets its
    expected per-device share times a skew-slack factor.  Join buffers
    are sized by the largest *raw* per-hop join over the candidate
    orders (cycle-closing hops equi-join before they filter, so their
    buffers must hold the pre-filter matches)."""
    from .cost_model import query_replications
    n_dev = 1
    for s in grid_shape:
        n_dev *= s

    def per(total):
        return int(total * slack / n_dev) + 256

    repl = max(query_replications(query.rel_dims(), grid_shape)) \
        if len(grid_shape) == query.n_dims else 1.0
    biggest = max(max(stats.sizes),
                  max((h for hops in stats.hop_joins for h in hops),
                      default=0.0))
    return ChainCaps(
        recv=per(max(stats.sizes) * repl),
        mid=per(biggest), out=per(biggest),
        local=per(max(stats.sizes) * repl),
        agg=per(stats.agg_groups or 256.0),
        join=per(biggest))


def default_chain_caps(stats: ChainStats, grid_shape: Sequence[int],
                       slack: int = 6) -> ChainCaps:
    """Size ChainCaps from exact statistics: each buffer gets its
    expected per-device share times a skew-slack factor.  ``slack``
    trades memory for overflow headroom (sort-merge buffers are linear
    in capacity, so generous slack is cheap; only the ``all_pairs``
    oracle pays quadratically)."""
    n_dev = 1
    for s in grid_shape:
        n_dev *= s

    def per(total):
        return int(total * slack / n_dev) + 256

    repl = max(chain_replications(stats.sizes, grid_shape)) \
        if len(grid_shape) == len(stats.sizes) - 1 else 1.0
    biggest = max(max(stats.sizes), max(stats.prefix_joins),
                  max(stats.pushdown_joins or (0.0,)))
    return ChainCaps(
        recv=per(max(stats.sizes) * repl),
        mid=per(biggest), out=per(biggest),
        local=per(max(stats.sizes) * repl),
        agg=per(max(stats.prefix_aggs or (256.0,))),
        join=per(stats.prefix_joins[-1]))


def default_mapside_caps(stats: ChainStats, num_partitions: int,
                         slack: int = 6) -> ChainCaps:
    """Size ChainCaps for ``mapside_cascade_chain``.

    Base relations never leave their stored partitions on proven hops,
    so ``mid``/``out`` only have to hold the per-device share of the
    intermediates (``prefix_joins``) — typically a fraction of the
    shuffle cascade's budget, which must also fit repartitioned base
    relations.  ``recv``/``local`` keep base-relation sizing for the
    unproven hops that fall back to shuffle or broadcast."""

    def per(total):
        return int(total * slack / num_partitions) + 256

    inter = per(max(stats.prefix_joins))
    return ChainCaps(
        recv=per(max(stats.sizes)), mid=inter, out=inter,
        local=per(max(stats.sizes)),
        agg=per(max(stats.prefix_aggs or (256.0,))),
        join=per(stats.prefix_joins[-1]))
