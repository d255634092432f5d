"""Per-device (per-"reducer") relational operators — the data plane.

These run inside one mesh shard (the reduce side of the paper's
MapReduce jobs) or inside the simulated grid (vmapped).  Everything is
static-shape: outputs have a caller-chosen capacity plus an overflow
flag.

The reduce-side hot path is **sort-merge**: :func:`sort_merge_join`
(one stable sort per input, searchsorted probe, prefix-sum pair
expansion — O(n log n + output) work and O(n + output) memory) and the
single-pass :func:`groupby_sum` (one lexicographic sort feeding the
``segment_sum`` kernel — Pallas on TPU, the bit-identical jnp oracle
elsewhere, per ``repro/kernels/ref.py``).  The quadratic all-pairs
join (:func:`local_join_allpairs`) and the multi-pass group-by
(:func:`groupby_sum_multipass`) are kept as the oracle references the
fast path is property-tested against; see docs/architecture.md
"Data plane".

The map-phase *hash partition* (bucket histogram + in-bucket rank)
likewise has a Pallas TPU kernel in ``repro.kernels``; the
implementation here is the pure-jnp semantics it must match.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..kernels import fused_join as fj
from ..kernels import ops
from .relation import Relation

_I32_MAX = jnp.iinfo(jnp.int32).max


# ---------------------------------------------------------------------------
# Hash partition (map-phase counting sort into destination buckets)
# ---------------------------------------------------------------------------

@jax.named_scope("join.partition")
def partition_ranks(bucket: jnp.ndarray, valid: jnp.ndarray, n_buckets: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stable counting-sort plan: for each element, its destination bucket
    rank (position within its bucket).

    Returns (order, sorted_bucket, rank) where ``order`` stably sorts
    elements by bucket (invalid last), ``rank[i]`` is the index of
    sorted element i within its bucket.

    The sorted keys come in runs, one per bucket, so a row's rank is its
    distance from the head of its run: ``first`` is a running max of the
    run heads' indices (one elementwise pass and one ``lax.cummax``, no
    gather).
    """
    key = jnp.where(valid, bucket, n_buckets)  # invalid rows sort last
    # Rank packing (kernels.fused_join): buckets are already dense ranks,
    # so one single-operand value sort replaces the permutation-carrying
    # stable argsort — bit-identical plan, ~an order of magnitude faster
    # on hosts whose multi-operand sort is the slow path.
    order = fj.partition_order(key, n_buckets)
    if order is None:                          # packed word would overflow
        order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    idx = jnp.arange(sorted_key.shape[0], dtype=jnp.int32)
    # First index of each row's bucket run: a run starts at row 0 or where
    # the key differs from the row before it.
    head = (sorted_key != jnp.roll(sorted_key, 1)) | (idx == 0)
    first = jax.lax.cummax(jnp.where(head, idx, 0), axis=0)
    rank = idx - first
    return order, sorted_key, rank


@jax.named_scope("join.partition")
def partition(rel: Relation, bucket: jnp.ndarray, n_buckets: int,
              cap_per_bucket: int) -> Tuple[Relation, jnp.ndarray]:
    """Scatter tuples into (n_buckets, cap_per_bucket) send buffers.

    This is the map-phase emit of the paper's algorithms: tuple ->
    destination reducer.  Returns a Relation whose columns have shape
    (n_buckets, cap_per_bucket) plus an overflow flag (any bucket fuller
    than its capacity).
    """
    order, sorted_bucket, rank = partition_ranks(bucket, rel.valid, n_buckets)
    in_range = (sorted_bucket < n_buckets) & (rank < cap_per_bucket)
    overflow = jnp.any((sorted_bucket < n_buckets) & (rank >= cap_per_bucket))
    dest = jnp.where(in_range, sorted_bucket * cap_per_bucket + rank,
                     n_buckets * cap_per_bucket)  # drop out-of-range
    total = n_buckets * cap_per_bucket

    def scatter(col):
        src = col[order]
        out = jnp.zeros((total + 1,), col.dtype).at[dest].set(src, mode="drop")
        return out[:total].reshape(n_buckets, cap_per_bucket)

    cols = {n: scatter(c) for n, c in rel.cols.items()}
    valid = (
        jnp.zeros((total + 1,), jnp.bool_)
        .at[dest].set(in_range, mode="drop")[:total]
        .reshape(n_buckets, cap_per_bucket)
    )
    return Relation(cols, valid), overflow


# ---------------------------------------------------------------------------
# Local equi-join (the reduce-side join within one reducer)
# ---------------------------------------------------------------------------

@jax.named_scope("join.emit")
def _emit_join_columns(left: Relation, right: Relation, left_key: str,
                       right_key: str, li_out: jnp.ndarray,
                       ri_out: jnp.ndarray, valid_out: jnp.ndarray,
                       prefix_l: str, prefix_r: str) -> Dict[str, jnp.ndarray]:
    """Gather output columns for matched (left-row, right-row) index
    pairs: the union of both inputs' columns, optional prefixes, the
    shared key emitted once under the left key's unprefixed name."""
    cols: Dict[str, jnp.ndarray] = {}
    for n, c in left.cols.items():
        name = n if n == left_key else prefix_l + n
        cols[name] = jnp.where(valid_out, c[li_out], jnp.zeros((), c.dtype))
    for n, c in right.cols.items():
        if n == right_key:
            continue  # key equal to left key; emitted once
        name = prefix_r + n
        if name in cols:
            raise ValueError(f"column collision {name!r}; use prefixes")
        cols[name] = jnp.where(valid_out, c[ri_out], jnp.zeros((), c.dtype))
    return cols


def _key_sentinel(dtype) -> int:
    """Padding sentinel for masked sorted keys: the dtype's max value
    (dtype-aware so int64 keys under x64 mode keep a sentinel above
    every real 64-bit id)."""
    return jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer) \
        else _I32_MAX


@jax.named_scope("join.sort")
def _sorted_by_key(key: jnp.ndarray, valid: jnp.ndarray,
                   presorted: bool = False
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable sort by (validity, key): valid rows first in ascending key
    order.  Returns (order, masked) where ``masked`` replaces the
    trailing invalid rows' keys with the dtype's max — non-decreasing
    even when a *valid* key equals the sentinel (callers clamp
    searchsorted results by the valid count to keep that collision
    harmless).

    ``presorted=True`` asserts the rows already satisfy the sort
    contract — valid rows first, ascending key (the layout
    :func:`sort_rows` and the partitioned store guarantee) — and skips
    the ``lax.sort`` entirely: the map-side merge-join fast path."""
    n = key.shape[0]
    n_valid = jnp.sum(valid).astype(jnp.int32)
    sentinel = _key_sentinel(key.dtype)
    if presorted:
        order = jnp.arange(n, dtype=jnp.int32)
        masked = jnp.where(jnp.arange(n) < n_valid, key, sentinel)
        return order, masked
    inv = (~valid).astype(jnp.int32)
    _, sorted_key, order = jax.lax.sort(
        (inv, key, jnp.arange(n, dtype=jnp.int32)), num_keys=2,
        is_stable=True)
    masked = jnp.where(jnp.arange(n) < n_valid, sorted_key, sentinel)
    return order, masked


@jax.named_scope("join.sort")
def sort_rows(rel: Relation, key: str) -> Relation:
    """Reorder a relation into the sorted-rows contract: valid rows
    first, ascending ``key`` (stable).  This is the layout
    :func:`sort_merge_join` can consume with ``presorted=True`` — the
    partitioned store sorts every partition this way on write."""
    order, _ = _sorted_by_key(rel.col(key), rel.valid)
    return rel.gather(order, jnp.ones(rel.valid.shape, jnp.bool_))


@jax.named_scope("join.emit")
def _probe_expand_emit(left: Relation, right: Relation, left_key: str,
                       right_key: str, out_capacity: int, prefix_l: str,
                       prefix_r: str, n_lv: jnp.ndarray, n_rv: jnp.ndarray,
                       l_order: jnp.ndarray, r_order: jnp.ndarray,
                       lo: jnp.ndarray, hi: jnp.ndarray,
                       ) -> Tuple[Relation, jnp.ndarray]:
    """Shared tail of the sorted-probe join — everything downstream of
    the per-side sorts and the raw ``lo/hi`` run bounds: valid-count
    clamping, the saturating prefix scan, pair expansion, and column
    emit.  Both the staged :func:`sort_merge_join` and the fused
    pipeline (:func:`fused_sort_merge_join`) end here, which is what
    makes their outputs bit-identical by construction."""
    nl = l_order.shape[0]
    nr = r_order.shape[0]
    # Clamping by the valid count drops the sentinel tail (incl. the
    # INT32_MAX collision).
    lo = jnp.minimum(lo, n_rv)
    hi = jnp.minimum(hi, n_rv)
    cnt = jnp.where(jnp.arange(nl) < n_lv, hi - lo, 0).astype(jnp.int32)

    # Inclusive scan of the counts, *saturating* at out_capacity + 1: a
    # plain int32 cumsum wraps once total matches exceed 2^31 (a 64k×64k
    # heavy-hitter reducer has 2^32), silently clearing the overflow
    # flag.  Saturating add is associative for inputs clamped to the
    # cap, and below the cap the scan equals the true prefix — which is
    # all the output ever reads: slots only go up to out_capacity − 1.
    cap1 = jnp.int32(out_capacity + 1)
    ends = jax.lax.associative_scan(
        lambda a, b: jnp.minimum(a + b, cap1), jnp.minimum(cnt, cap1))
    n_match = ends[-1]                        # min(total matches, cap + 1)
    overflow = n_match > out_capacity

    # Pair expansion: output slot s belongs to the first sorted-left row
    # whose inclusive prefix count exceeds s; its offset within that
    # row's run indexes the right-sorted range.  The owner's *start* is
    # the previous row's scan value (exact: every prefix before the
    # owner is below the cap, hence unsaturated).
    slot = jnp.arange(out_capacity, dtype=jnp.int32)
    owner = jnp.searchsorted(ends, slot, side="right")
    owner = jnp.clip(owner, 0, nl - 1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    off = slot - starts[owner]
    r_pos = jnp.clip(lo[owner] + off, 0, nr - 1)

    valid_out = slot < n_match
    li_out = l_order[owner]
    ri_out = r_order[r_pos]
    cols = _emit_join_columns(left, right, left_key, right_key,
                              li_out, ri_out, valid_out, prefix_l, prefix_r)
    return Relation(cols, valid_out), overflow


def _check_out_capacity(out_capacity: int) -> None:
    # Bound so the saturating scan's combine (a + b with a, b <= cap1)
    # stays within int32: 2·(out_capacity + 1) must not reach 2^31.
    if not 0 < out_capacity < 2 ** 30 - 1:
        raise ValueError(f"out_capacity must be in (0, 2^30 - 1), got "
                         f"{out_capacity}")


def sort_merge_join(left: Relation, right: Relation, left_key: str,
                    right_key: str, out_capacity: int,
                    prefix_l: str = "", prefix_r: str = "",
                    presorted_l: bool = False, presorted_r: bool = False,
                    ) -> Tuple[Relation, jnp.ndarray]:
    """Equi-join two local relations on ``left_key == right_key`` by
    sorted probe — the data-plane fast path.

    One stable sort per input, then for every left row a
    ``searchsorted(left)/searchsorted(right)`` run-length match count,
    an exclusive prefix sum assigning contiguous output slots, and a
    static-capacity gather expanding the match pairs — O((n + output)
    log n) work and O(n + output) memory, never the ``nl×nr``
    intermediate of :func:`local_join_allpairs`.

    Output semantics match the all-pairs oracle exactly as a *set*:
    same matched tuples, same overflow flag (total matches >
    ``out_capacity``).  Only the row order differs (key order here,
    left-major row order there) — and, under overflow, which subset of
    matches is kept.

    ``presorted_l`` / ``presorted_r`` assert the corresponding input
    already satisfies the sorted-rows contract (valid first, ascending
    key — :func:`sort_rows` / the partitioned store) and skip that
    input's ``lax.sort``: the map-side merge-join fast path.  Rows that
    violate the contract silently mis-join, so only pass the flags for
    inputs whose layout is *proven* (e.g. loaded from a sorted
    partition manifest).
    """
    _check_out_capacity(out_capacity)
    lk, rk = left.col(left_key), right.col(right_key)
    n_lv = jnp.sum(left.valid).astype(jnp.int32)
    n_rv = jnp.sum(right.valid).astype(jnp.int32)

    l_order, lk_m = _sorted_by_key(lk, left.valid, presorted=presorted_l)
    r_order, rk_m = _sorted_by_key(rk, right.valid, presorted=presorted_r)

    # Run-length probe: matches of sorted-left row i live in
    # right-sorted positions [lo[i], hi[i]).
    with jax.named_scope("join.probe"):
        lo = jnp.searchsorted(rk_m, lk_m, side="left")
        hi = jnp.searchsorted(rk_m, lk_m, side="right")
    return _probe_expand_emit(left, right, left_key, right_key, out_capacity,
                              prefix_l, prefix_r, n_lv, n_rv,
                              l_order, r_order, lo, hi)


def fused_sort_merge_join(left: Relation, right: Relation, left_key: str,
                          right_key: str, out_capacity: int,
                          prefix_l: str = "", prefix_r: str = "",
                          presorted_l: bool = False, presorted_r: bool = False,
                          probe_backend: str = "auto",
                          ) -> Tuple[Relation, jnp.ndarray]:
    """The fused partition→sort→probe pipeline, ``join_impl="fused"``.

    Same contract as :func:`sort_merge_join` and **bit-identical** to
    it (the property suite asserts full-array equality, padding
    included): the per-side stable (validity, key) sorts run as rank
    packing — two single-operand value sorts instead of one
    permutation-carrying multi-operand sort, ~2× the whole join at 16k
    rows on CPU hosts — and the probe's run bounds go through
    :func:`repro.kernels.fused_join.probe_counts`, whose Pallas kernel
    streams key blocks through VMEM with the grid pipeline
    double-buffering each block's DMA (``ref`` = the staged path's own
    ``searchsorted`` elsewhere).  Everything downstream — clamping,
    saturating scan, pair expansion, emit — is literally the shared
    code the staged path runs (:func:`_probe_expand_emit`).

    ``presorted_*`` inputs already satisfy the sorted-rows contract, so
    there is nothing to fuse on that side; they take the same skip as
    the staged path.
    """
    _check_out_capacity(out_capacity)
    lk, rk = left.col(left_key), right.col(right_key)
    n_lv = jnp.sum(left.valid).astype(jnp.int32)
    n_rv = jnp.sum(right.valid).astype(jnp.int32)

    if presorted_l:
        l_order, lk_m = _sorted_by_key(lk, left.valid, presorted=True)
    else:
        l_order, lk_m = fj.stable_key_order(lk, left.valid)
    if presorted_r:
        r_order, rk_m = _sorted_by_key(rk, right.valid, presorted=True)
    else:
        r_order, rk_m = fj.stable_key_order(rk, right.valid)

    lo, hi = fj.probe_counts(lk_m, rk_m, backend=probe_backend)
    return _probe_expand_emit(left, right, left_key, right_key, out_capacity,
                              prefix_l, prefix_r, n_lv, n_rv,
                              l_order, r_order, lo, hi)


@jax.named_scope("join.probe")
def local_join_allpairs(left: Relation, right: Relation, left_key: str,
                        right_key: str, out_capacity: int,
                        prefix_l: str = "", prefix_r: str = "",
                        presorted_l: bool = False, presorted_r: bool = False,
                        ) -> Tuple[Relation, jnp.ndarray]:
    """Equi-join two local relations on ``left_key == right_key``.

    All-pairs compare with masks (static shape) — the **oracle
    reference** for :func:`sort_merge_join`: O(nl·nr) compute and
    memory, simple enough to be obviously correct.  Used by the
    property-based equivalence suite and available to the executor via
    ``join_impl="all_pairs"``.  Structurally limited to nl·nr < 2^31
    (the flat pair index is int32); sort-merge has no such limit.
    ``presorted_l``/``presorted_r`` are accepted for interface parity
    and ignored — the all-pairs compare needs no sort either way.
    """
    del presorted_l, presorted_r
    lk, rk = left.col(left_key), right.col(right_key)
    if lk.shape[0] * rk.shape[0] >= 2 ** 31:
        raise ValueError(
            f"all_pairs flat pair index overflows int32: "
            f"{lk.shape[0]} x {rk.shape[0]} = {lk.shape[0] * rk.shape[0]} "
            f">= 2^31 pairs.  Use join_impl='sort_merge' (no pair-count "
            f"limit) or shrink the per-device capacities.")
    match = (lk[:, None] == rk[None, :]) & left.valid[:, None] & right.valid[None, :]
    flat = match.reshape(-1)
    # Exclusive prefix count = output slot of each matching pair.
    slot = jnp.cumsum(flat) - flat
    n_match = jnp.sum(flat)
    overflow = n_match > out_capacity
    dest = jnp.where(flat & (slot < out_capacity), slot, out_capacity)

    nl, nr = lk.shape[0], rk.shape[0]
    li = (jnp.arange(nl * nr, dtype=jnp.int32) // nr)
    ri = (jnp.arange(nl * nr, dtype=jnp.int32) % nr)
    li_out = jnp.zeros((out_capacity + 1,), jnp.int32).at[dest].set(li, mode="drop")[:out_capacity]
    ri_out = jnp.zeros((out_capacity + 1,), jnp.int32).at[dest].set(ri, mode="drop")[:out_capacity]
    valid_out = (
        jnp.zeros((out_capacity + 1,), jnp.bool_).at[dest].set(flat, mode="drop")[:out_capacity]
    )
    cols = _emit_join_columns(left, right, left_key, right_key,
                              li_out, ri_out, valid_out, prefix_l, prefix_r)
    return Relation(cols, valid_out), overflow


JOIN_IMPLS = {
    "sort_merge": sort_merge_join,
    "fused": fused_sort_merge_join,
    "all_pairs": local_join_allpairs,
}


def local_join(left: Relation, right: Relation, left_key: str, right_key: str,
               out_capacity: int,
               prefix_l: str = "", prefix_r: str = "",
               impl: str = "sort_merge",
               presorted_l: bool = False, presorted_r: bool = False,
               ) -> Tuple[Relation, jnp.ndarray]:
    """Equi-join two local relations on ``left_key == right_key``.

    Dispatches to :func:`sort_merge_join` (default) or the all-pairs
    oracle (``impl="all_pairs"``).  Both return the same matched-tuple
    set and overflow flag; only the row order (and, under overflow,
    which matches are kept) differs.  ``presorted_l``/``presorted_r``
    forward the sorted-rows assertion to the sort-merge path (ignored
    by all-pairs).
    """
    try:
        fn = JOIN_IMPLS[impl]
    except KeyError:
        raise ValueError(
            f"unknown join impl {impl!r}; one of {sorted(JOIN_IMPLS)}")
    return fn(left, right, left_key, right_key, out_capacity,
              prefix_l=prefix_l, prefix_r=prefix_r,
              presorted_l=presorted_l, presorted_r=presorted_r)


# ---------------------------------------------------------------------------
# Local group-by-sum (the aggregation hot-spot; paper Section V)
# ---------------------------------------------------------------------------

def _group_heads(sorted_valid: jnp.ndarray, sorted_keys) -> Tuple[jnp.ndarray,
                                                                  jnp.ndarray]:
    """Given rows sorted by (validity, *keys): the group-head mask and
    per-row group index (cumsum of heads − 1)."""
    cap = sorted_valid.shape[0]
    prev_same = jnp.ones((cap,), jnp.bool_)
    for sk in sorted_keys:
        prev_same = prev_same & (sk == jnp.roll(sk, 1))
    head = sorted_valid & (~prev_same | (jnp.arange(cap) == 0))
    seg_id = jnp.cumsum(head.astype(jnp.int32)) - 1
    return head, seg_id


@jax.named_scope("join.groupby")
def groupby_sum(rel: Relation, keys: Tuple[str, ...], value: str,
                out_capacity: int | None = None, *, backend: str = "auto",
                ) -> Tuple[Relation, jnp.ndarray]:
    """SUM ``value`` grouped by ``keys`` — the single-pass data-plane
    aggregator.

    One stable multi-key ``lax.sort`` orders the rows by the composite
    key tuple (validity most significant, so padding sorts last) in a
    single fused pass; run heads become segment ids and the per-segment
    sums go through :func:`repro.kernels.ops.segment_sum` — the Pallas
    MXU kernel on TPU, the bit-identical jnp oracle elsewhere.  Matches
    the paper's aggregator: for matrix multiply, keys=("a","c") and
    value="p".  Output capacity defaults to the input capacity;
    ``overflow`` is raised when the group count exceeds it (the
    surviving groups are the first ``out_capacity`` in key order, same
    as the multipass oracle).
    """
    cap = rel.capacity
    out_cap = out_capacity if out_capacity is not None else cap
    inv = (~rel.valid).astype(jnp.int32)
    operands = (inv,) + tuple(rel.cols[k] for k in keys) + (
        jnp.arange(cap, dtype=jnp.int32),)
    sorted_ops = jax.lax.sort(operands, num_keys=1 + len(keys), is_stable=True)
    order = sorted_ops[-1]
    sorted_valid = rel.valid[order]
    sorted_keys = sorted_ops[1:1 + len(keys)]
    sorted_val = rel.cols[value][order].astype(jnp.float32)

    head, seg_id = _group_heads(sorted_valid, sorted_keys)
    n_groups = jnp.sum(head)
    overflow = n_groups > out_cap

    # Segment ids are non-decreasing over the valid prefix — exactly the
    # sorted-ids case whose staircase the Pallas kernel walks.
    # Invalid / overflowed rows get id out_cap, dropped by the kernel.
    seg = jnp.where(sorted_valid, seg_id, out_cap)
    sums = ops.segment_sum(jnp.where(sorted_valid, sorted_val, 0.0), seg,
                           out_cap, backend=backend, indices_are_sorted=True)
    dest = jnp.where(sorted_valid & (seg_id < out_cap), seg_id, out_cap)
    out_cols = {}
    for k, sk in zip(keys, sorted_keys):
        out_cols[k] = jnp.zeros((out_cap + 1,), sk.dtype).at[dest].set(
            sk, mode="drop")[:out_cap]
    out_cols[value] = sums
    valid_out = jnp.arange(out_cap) < n_groups
    return Relation(out_cols, valid_out), overflow


@jax.named_scope("join.groupby")
def groupby_sum_multipass(rel: Relation, keys: Tuple[str, ...], value: str,
                          out_capacity: int | None = None
                          ) -> Tuple[Relation, jnp.ndarray]:
    """SUM ``value`` grouped by ``keys`` (lexicographic argsort chain +
    scatter-add) — the **oracle reference** for :func:`groupby_sum`:
    ``len(keys)+1`` full argsorts, kept for the property-based
    equivalence suite.
    """
    cap = rel.capacity
    out_cap = out_capacity if out_capacity is not None else cap
    # Stable lexicographic sort: least-significant key first.
    order = jnp.arange(cap, dtype=jnp.int32)
    for k in reversed(keys):
        col = rel.cols[k][order]
        col = jnp.where(rel.valid[order], col, _key_sentinel(col.dtype))
        order = order[jnp.argsort(col, stable=True)]
    # Invalid rows last: final pass on validity.
    order = order[jnp.argsort(~rel.valid[order], stable=True)]

    sorted_valid = rel.valid[order]
    sorted_keys = [rel.cols[k][order] for k in keys]
    sorted_val = rel.cols[value][order].astype(jnp.float32)

    head, seg_id = _group_heads(sorted_valid, sorted_keys)
    n_groups = jnp.sum(head)
    overflow = n_groups > out_cap

    dest = jnp.where(sorted_valid & (seg_id < out_cap), seg_id, out_cap)
    sums = jnp.zeros((out_cap + 1,), jnp.float32).at[dest].add(
        jnp.where(sorted_valid, sorted_val, 0.0))[:out_cap]
    out_cols = {}
    for k, sk in zip(keys, sorted_keys):
        out_cols[k] = jnp.zeros((out_cap + 1,), sk.dtype).at[dest].set(
            sk, mode="drop")[:out_cap]
    out_cols[value] = sums
    valid_out = jnp.arange(out_cap) < n_groups
    return Relation(out_cols, valid_out), overflow
