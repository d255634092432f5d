"""The fused partition → sort → probe join pipeline (PR: overlapped
execution path).

The staged data plane runs one stable multi-operand ``lax.sort`` per
input — ``sort((validity, key, iota), num_keys=2)`` — whose
permutation-carrying comparator is the hot spot of every reduce-side
join (≈ 12 ms per 16k-row side on a CPU host, vs ≈ 1 ms for a
single-operand value sort).  This module collapses that cost with a
**rank packing** identity and streams the probe through a Pallas
kernel:

* :func:`stable_key_order` — the stable argsort by ``(validity, key)``
  computed as *two single-operand sorts*: sort the raw key values
  (fast path), dense-rank every row by ``searchsorted``, pack
  ``(validity, rank, row)`` into one integer word, sort the packed
  words, unpack the row indices.  The packed order is **bit-identical**
  to the staged ``lax.sort`` order: ranks are strictly monotone in the
  key, the validity bit is the most-significant digit, and the row
  index tiebreak reproduces stability exactly.

* :func:`partition_order` — the same packing applied to the map-phase
  hash partition (buckets are already dense ranks), replacing the
  stable ``argsort`` inside ``partition_ranks``.

* :func:`probe_counts` — the merge-probe ``lo/hi`` run bounds as
  *counting* (``lo = #{r < q}``, ``hi = #{r ≤ q}``, equal to
  ``searchsorted`` left/right on the sorted side), with a Pallas TPU
  kernel that streams (query-block × key-block) tiles through VMEM —
  the grid pipeline double-buffers each block's DMA against the
  previous block's compute — and prunes off-band tiles with
  ``pl.when`` (sorted inputs leave only the diagonal band dense).
  Backend policy follows ``repro.kernels.backend``: ``pallas`` on TPU,
  ``interpret`` for CPU validation, ``ref`` (= ``jnp.searchsorted``,
  the staged path's own op) elsewhere.

``core.local.fused_sort_merge_join`` assembles these into
``join_impl="fused"``; the staged ``sort_merge_join`` stays the
bit-identical oracle (see tests/test_fused_join.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend as _backend

# Block row of the (1, N) lane layout; a typed int32 so that index maps
# stay 32-bit under x64.
_ROW = np.int32(0)

_I32_MAX = jnp.iinfo(jnp.int32).max


def _key_sentinel(dtype) -> int:
    """Padding sentinel for masked sorted keys (same convention as
    ``core.local``): the dtype's max value."""
    return jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer) \
        else _I32_MAX


def _pack_dtype(n: int, n_ranks: int):
    """Dtype that can hold ``rank * n + row`` for every rank in
    [0, n_ranks) and row in [0, n) — int32 when the largest packed word
    ``n_ranks·n − 1`` fits, int64 when x64 is live, else ``None``
    (caller falls back to the staged ``lax.sort``)."""
    if n <= 1:
        return jnp.int32
    if n_ranks * n - 1 <= _I32_MAX:
        return jnp.int32
    if jax.config.read("jax_enable_x64"):
        return jnp.int64
    return None


def _packed_stable_argsort(rank: jnp.ndarray, n_ranks: int) -> Optional[jnp.ndarray]:
    """Stable argsort of a dense-rank vector via one single-operand
    sort: pack ``rank·n + row`` (distinct words, lexicographic in
    (rank, row)), sort values only, unpack the rows.  Returns ``None``
    when no integer dtype can hold the packed words."""
    n = rank.shape[0]
    dt = _pack_dtype(n, n_ranks)
    if dt is None:
        return None
    packed = rank.astype(dt) * jnp.asarray(n, dt) + jnp.arange(n, dtype=dt)
    return (jnp.sort(packed) % jnp.asarray(max(n, 1), dt)).astype(jnp.int32)


@jax.named_scope("join.sort")
def stable_key_order(key: jnp.ndarray, valid: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable sort order by (validity, key) — bit-identical to
    ``core.local._sorted_by_key`` — via rank packing.

    Returns ``(order, masked)``: ``order`` is the stable permutation
    (valid rows first in ascending key order), ``masked`` the sorted
    keys with the invalid tail replaced by the dtype sentinel.

    Identity argument: with ``rk[i] = #{j : key[j] < key[i]}`` (one
    value sort + one ``searchsorted``), ``key[a] < key[b] ⇔ rk[a] <
    rk[b]`` and equal keys share a rank, so ordering by the packed word
    ``(inv·n + rk)·n + i`` is exactly the stable (validity, key, row)
    order the staged ``lax.sort`` produces.  When the packed word
    cannot fit an integer dtype (rows > 2^15 without x64) this falls
    back to the staged sort itself — still bit-identical, just not
    faster.
    """
    n = key.shape[0]
    n_valid = jnp.sum(valid).astype(jnp.int32)
    sentinel = _key_sentinel(key.dtype)
    inv = (~valid).astype(jnp.int32)
    dt = _pack_dtype(n, 2 * n)
    if dt is None:
        _, sorted_key, order = jax.lax.sort(
            (inv, key, jnp.arange(n, dtype=jnp.int32)), num_keys=2,
            is_stable=True)
    else:
        skey = jnp.sort(key)                       # single-operand fast path
        rk = jnp.searchsorted(skey, key, side="left").astype(jnp.int32)
        rank = inv * jnp.int32(n) + rk             # dense (validity, key) rank
        order = _packed_stable_argsort(rank, 2 * n)
        sorted_key = key[order]
    masked = jnp.where(jnp.arange(n) < n_valid, sorted_key, sentinel)
    return order, masked


def partition_order(bucket_key: jnp.ndarray, n_buckets: int
                    ) -> Optional[jnp.ndarray]:
    """Stable argsort of a dense bucket-key vector (values in
    [0, n_buckets], invalid rows already mapped to ``n_buckets``) — the
    map-phase counting-sort plan of ``partition_ranks``, via the same
    packing.  Returns ``None`` when the packed word would overflow
    (caller keeps the plain stable argsort)."""
    return _packed_stable_argsort(bucket_key, n_buckets + 1)


# ---------------------------------------------------------------------------
# Merge-probe run bounds: the Pallas streaming kernel
# ---------------------------------------------------------------------------

def _probe_kernel(q_ref, r_ref, lo_ref, hi_ref, *, block_r: int):
    """One (query-block × key-block) tile: add this key block's
    contribution to every query's ``lo``/``hi`` count.

    The grid's minor axis streams key blocks through VMEM — Pallas
    double-buffers the next block's copy against this block's compute —
    and the ``pl.when`` guards prune tiles off the diagonal band (both
    inputs sorted): a block wholly below the query range contributes a
    constant, wholly above contributes nothing, and only boundary
    blocks pay the dense compare."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    q = q_ref[0, :]
    r = r_ref[0, :]
    q_min = jnp.min(q)
    q_max = jnp.max(q)
    r_min = r[0]
    r_max = r[block_r - 1]

    @pl.when(r_max < q_min)          # whole block below every query
    def _all_below():
        lo_ref[...] += jnp.int32(block_r)
        hi_ref[...] += jnp.int32(block_r)

    @pl.when((r_max >= q_min) & (r_min <= q_max))   # boundary band: compare
    def _band():
        lt = jnp.sum((r[None, :] < q[:, None]).astype(jnp.int32), axis=1,
                     dtype=jnp.int32)
        le = jnp.sum((r[None, :] <= q[:, None]).astype(jnp.int32), axis=1,
                     dtype=jnp.int32)
        lo_ref[...] += lt[None, :]
        hi_ref[...] += le[None, :]


@functools.partial(jax.jit, static_argnames=("block_q", "block_r",
                                             "interpret"))
def probe_counts_pallas(queries: jnp.ndarray, sorted_keys: jnp.ndarray, *,
                        block_q: int = 512, block_r: int = 512,
                        interpret: bool = False
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(lo, hi)`` run bounds of every query in a sorted key column:
    ``lo = #{r < q}``, ``hi = #{r ≤ q}`` — equal to ``searchsorted``
    left/right.  Sorted-key padding uses the dtype sentinel; the counts
    are clamped to the true key count so sentinel padding never leaks
    (the same clamp the callers apply with the valid count).

    Both columns lie along the lane axis, ``(1, n)`` tiled by
    ``(1, block)`` blocks, as Mosaic's (8, 128) block rule asks."""
    nq, nr = queries.shape[0], sorted_keys.shape[0]
    sentinel = _key_sentinel(sorted_keys.dtype)
    block_q = min(block_q, max(128, 1 << (max(nq, 1) - 1).bit_length()))
    block_r = min(block_r, max(128, 1 << (max(nr, 1) - 1).bit_length()))
    pad_q = -nq % block_q
    pad_r = -nr % block_r
    q = jnp.pad(queries, (0, pad_q), constant_values=sentinel)
    r = jnp.pad(sorted_keys, (0, pad_r), constant_values=sentinel)
    n_qb = (nq + pad_q) // block_q
    n_rb = (nr + pad_r) // block_r

    lo, hi = pl.pallas_call(
        functools.partial(_probe_kernel, block_r=block_r),
        grid=(n_qb, n_rb),
        in_specs=[
            pl.BlockSpec((1, block_q), lambda i, j: (_ROW, i)),
            pl.BlockSpec((1, block_r), lambda i, j: (_ROW, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q), lambda i, j: (_ROW, i)),
            pl.BlockSpec((1, block_q), lambda i, j: (_ROW, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_qb * block_q), jnp.int32),
            jax.ShapeDtypeStruct((1, n_qb * block_q), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="probe_counts",
    )(q.reshape(1, -1), r.reshape(1, -1))
    lo = jnp.minimum(lo.reshape(-1)[:nq], nr)
    hi = jnp.minimum(hi.reshape(-1)[:nq], nr)
    return lo, hi


@jax.named_scope("join.probe")
def probe_counts(queries: jnp.ndarray, sorted_keys: jnp.ndarray, *,
                 backend: str = "auto", block_q: int = 512,
                 block_r: int = 512) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dispatching wrapper (policy of ``repro.kernels.backend``): ``ref``
    is ``jnp.searchsorted`` left/right — the exact op the staged path
    runs, so the fused pipeline is bit-identical to the oracle on every
    backend that resolves to it.

    The TPU has no 64-bit vector lanes, so 64-bit keys on the
    ``pallas`` backend raise ``TypeError`` (x64 joins on the chip take
    ``join_impl="sort_merge"``)."""
    b = _backend.resolve(backend)
    if b == "pallas" and max(queries.dtype.itemsize,
                             sorted_keys.dtype.itemsize) == 8:
        raise TypeError(
            f"probe_counts: the Pallas TPU kernel takes 32-bit keys, got "
            f"{queries.dtype}/{sorted_keys.dtype}; use "
            f"join_impl='sort_merge' for 64-bit keys")
    if b == "ref":
        lo = jnp.searchsorted(sorted_keys, queries, side="left")
        hi = jnp.searchsorted(sorted_keys, queries, side="right")
        return lo, hi
    return probe_counts_pallas(queries, sorted_keys, block_q=block_q,
                               block_r=block_r, interpret=(b == "interpret"))
