"""The fused rank-packed join pipeline vs the staged oracle.

``fused_sort_merge_join`` must be **bit-identical** to
``sort_merge_join`` — same output rows in the same order, same padding,
same overflow flag — because the staged path is the oracle the fused
kernel is verified against (``join_impl`` selects between them at every
level of the engine).  The curated case matrix always runs; the
randomized sweep additionally runs when hypothesis is installed
(``pip install -e .[dev]``).

Covered hazards, each of which broke a draft of the kernel:

* all-invalid inputs (the rank packing must not let sentinel rows
  alias real keys),
* a *valid* key equal to the int32 sentinel (searchsorted results are
  clamped by the valid count),
* matches exactly at ``out_capacity`` (no overflow) and one past it
  (overflow, same flag as staged),
* the packed-rank int32 overflow bound (large capacity falls back to
  the staged ``lax.sort`` — parity, not divergence).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Relation, SimGrid
from repro.core.local import (_sorted_by_key, fused_sort_merge_join,
                              local_join, partition_ranks, sort_merge_join,
                              sort_rows)
from repro.kernels import fused_join as fj

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

I32_MAX = np.iinfo(np.int32).max


def rel(keys, vals=None, capacity=None, valid=None, key_name="b",
        val_name="v"):
    keys = np.asarray(keys, np.int32)
    n = len(keys)
    cap = capacity if capacity is not None else n
    cols = {key_name: np.zeros(cap, np.int32),
            val_name: np.zeros(cap, np.float32)}
    cols[key_name][:n] = keys
    cols[val_name][:n] = (np.arange(n, dtype=np.float32) + 1.0
                          if vals is None else np.asarray(vals, np.float32))
    v = np.zeros(cap, bool)
    v[:n] = True if valid is None else np.asarray(valid, bool)
    return Relation({k: jnp.asarray(c) for k, c in cols.items()},
                    jnp.asarray(v))


def assert_bit_identical(case, left, right, out_cap, **kw):
    o1, f1 = sort_merge_join(left, right, "b", "b", out_cap, **kw)
    o2, f2 = fused_sort_merge_join(left, right, "b", "b", out_cap, **kw)
    assert bool(f1) == bool(f2), (case, "overflow flag")
    assert o1.names == o2.names, case
    assert np.array_equal(np.asarray(o1.valid), np.asarray(o2.valid)), case
    for name in o1.names:
        assert np.array_equal(np.asarray(o1.cols[name]),
                              np.asarray(o2.cols[name])), (case, name)


CASES = {
    "plain": (rel([3, 1, 4, 1, 5]), rel([1, 1, 2, 3], val_name="w"), 32),
    "empty_left": (rel([], capacity=8), rel([1, 2, 3], val_name="w"), 16),
    "all_invalid": (rel([7, 7, 7], valid=[False] * 3),
                    rel([7, 7], val_name="w"), 16),
    "both_invalid": (rel([2, 2], valid=[False] * 2),
                     rel([2, 2], valid=[False] * 2, val_name="w"), 8),
    "sentinel_key": (rel([I32_MAX, 2, I32_MAX]),
                     rel([I32_MAX, 2], val_name="w"), 16),
    "duplicates": (rel([5] * 8), rel([5] * 8, val_name="w"), 64),
    "no_matches": (rel([1, 2, 3]), rel([4, 5, 6], val_name="w"), 8),
    "holes": (rel([9, 9, 2, 4], capacity=8,
                  valid=[True, False, True, True]),
              rel([9, 2, 2], capacity=6, val_name="w"), 32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_bit_identical(case):
    left, right, out_cap = CASES[case]
    assert_bit_identical(case, left, right, out_cap)


def test_fused_exact_capacity_and_overflow():
    # 3 x 4 = 12 matches on key 5: exactly at out_capacity=12 (no
    # overflow), over it at 11 (overflow) — the flags and rows must
    # match staged in both regimes.
    left = rel([5, 5, 5])
    right = rel([5, 5, 5, 5], val_name="w")
    assert_bit_identical("exact_capacity", left, right, 12)
    o, f = fused_sort_merge_join(left, right, "b", "b", 12)
    assert not bool(f) and int(np.sum(np.asarray(o.valid))) == 12
    assert_bit_identical("overflow", left, right, 11)
    _, f = fused_sort_merge_join(left, right, "b", "b", 11)
    assert bool(f)


def test_fused_prefixes_and_presorted():
    left = rel([4, 2, 2, 7])
    right = rel([2, 7, 7], val_name="v")  # name collision: prefixes
    assert_bit_identical("prefixes", left, right, 32,
                         prefix_l="l_", prefix_r="r_")
    ls, rs = sort_rows(left, "b"), sort_rows(right, "b")
    o1, f1 = sort_merge_join(ls, rs, "b", "b", 32, prefix_r="r_",
                             presorted_l=True, presorted_r=True)
    o2, f2 = fused_sort_merge_join(ls, rs, "b", "b", 32, prefix_r="r_",
                                   presorted_l=True, presorted_r=True)
    assert bool(f1) == bool(f2)
    for name in o1.names:
        assert np.array_equal(np.asarray(o1.cols[name]),
                              np.asarray(o2.cols[name])), name


def test_stable_key_order_matches_staged_sort():
    rng = np.random.default_rng(0)
    for n, n_keys in ((1, 1), (7, 3), (64, 5), (128, 128), (257, 11)):
        key = jnp.asarray(rng.integers(0, n_keys, n), jnp.int32)
        valid = jnp.asarray(rng.random(n) < 0.8)
        o1, m1 = _sorted_by_key(key, valid)
        o2, m2 = fj.stable_key_order(key, valid)
        assert np.array_equal(np.asarray(o1), np.asarray(o2)), n
        assert np.array_equal(np.asarray(m1), np.asarray(m2)), n


def test_stable_key_order_packing_fallback():
    # Past the int32 packing bound the fused sort must fall back to the
    # staged lax.sort — identical results either way.
    n = 1 << 16
    rng = np.random.default_rng(1)
    key = jnp.asarray(rng.integers(0, I32_MAX, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.9)
    assert fj._pack_dtype(n, 2 * n) is None  # 2n·n − 1 > int32 max
    o1, m1 = _sorted_by_key(key, valid)
    o2, m2 = fj.stable_key_order(key, valid)
    assert np.array_equal(np.asarray(o1), np.asarray(o2))
    assert np.array_equal(np.asarray(m1), np.asarray(m2))


def test_partition_order_matches_argsort():
    rng = np.random.default_rng(2)
    for n, k in ((1, 1), (16, 4), (100, 7), (256, 16)):
        bucket = jnp.asarray(rng.integers(0, k, n), jnp.int32)
        order = fj.partition_order(bucket, k)
        assert order is not None
        want = jnp.argsort(bucket, stable=True)
        assert np.array_equal(np.asarray(order), np.asarray(want)), (n, k)


def test_partition_ranks_matches_argsort_plan():
    rng = np.random.default_rng(3)
    for n, k in ((1, 1), (64, 8), (200, 13)):
        bucket = jnp.asarray(rng.integers(0, k, n), jnp.int32)
        valid = jnp.asarray(rng.random(n) < 0.7)
        order, sorted_key, rank = partition_ranks(bucket, valid, k)
        # reference plan: plain stable argsort of the same key
        key = np.where(np.asarray(valid), np.asarray(bucket), k)
        want_order = np.argsort(key, kind="stable")
        want_sorted = key[want_order]
        first = np.searchsorted(want_sorted, want_sorted, side="left")
        want_rank = np.arange(n) - first
        assert np.array_equal(np.asarray(order), want_order), (n, k)
        assert np.array_equal(np.asarray(sorted_key), want_sorted), (n, k)
        assert np.array_equal(np.asarray(rank), want_rank), (n, k)


def searchsorted_ranks(bucket, valid, n_buckets):
    """The oracle: ``partition_ranks`` with each row's run head found by
    a binary search of the sorted keys against themselves."""
    key = jnp.where(valid, bucket, n_buckets)
    order = fj.partition_order(key, n_buckets)
    if order is None:
        order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    first = jnp.searchsorted(sorted_key, sorted_key, side="left")
    rank = jnp.arange(sorted_key.shape[0], dtype=jnp.int32) - first.astype(
        jnp.int32)
    return order, sorted_key, rank


def bucket_case(fill, n, k, rng):
    """(bucket, valid) of ``n`` rows over ``k`` buckets: every row
    invalid, every row in one bucket, or every bucket used (while n ≥ k)
    with every seventh row invalid."""
    if fill == "all_invalid":
        return rng.integers(0, k, n), np.zeros(n, bool)
    if fill == "one_bucket":
        return np.full(n, rng.integers(0, k)), np.ones(n, bool)
    return rng.permutation(np.arange(n) % k), np.arange(n) % 7 != 6


@pytest.mark.parametrize("mode", ["plain", "jit", "vmap"])
@pytest.mark.parametrize("fill", ["all_invalid", "one_bucket",
                                  "every_bucket"])
@pytest.mark.parametrize("k", [1, 4, 16, 64])
@pytest.mark.parametrize("n", [0, 1, 2, 1000])
def test_partition_ranks_matches_searchsorted(n, k, fill, mode):
    rng = np.random.default_rng([n, k, len(fill)])
    lanes = 16 if mode == "vmap" else 1
    cases = [bucket_case(fill, n, k, rng) for _ in range(lanes)]
    bucket = jnp.asarray(np.stack([b for b, _ in cases]), jnp.int32)
    valid = jnp.asarray(np.stack([v for _, v in cases]))
    if mode == "vmap":
        got = jax.vmap(lambda b, v: partition_ranks(b, v, k))(bucket, valid)
        want = jax.vmap(lambda b, v: searchsorted_ranks(b, v, k))(bucket,
                                                                   valid)
    else:
        run = jax.jit(partition_ranks, static_argnums=2) if mode == "jit" \
            else partition_ranks
        got = run(bucket[0], valid[0], k)
        want = searchsorted_ranks(bucket[0], valid[0], k)
    for name, g, w in zip(("order", "sorted_key", "rank"), got, want):
        assert g.dtype == w.dtype, name
        assert np.array_equal(np.asarray(g), np.asarray(w)), name


@pytest.mark.parametrize("cap", [25, 24])
def test_partition_matches_searchsorted_at_capacity(cap, monkeypatch):
    # 100 rows over 4 buckets, 25 in each: exactly at capacity (no
    # overflow) at 25, one row over it in every bucket at 24.
    from repro.core import local

    rng = np.random.default_rng(6)
    bucket = jnp.asarray(rng.permutation(np.arange(100) % 4), jnp.int32)
    r = rel(rng.integers(0, 50, 100))
    got, got_ovf = local.partition(r, bucket, 4, cap)
    monkeypatch.setattr(local, "partition_ranks", searchsorted_ranks)
    want, want_ovf = local.partition(r, bucket, 4, cap)
    assert bool(got_ovf) == bool(want_ovf) == (cap < 25)
    assert np.array_equal(np.asarray(got.valid), np.asarray(want.valid))
    for name in want.names:
        assert np.array_equal(np.asarray(got.cols[name]),
                              np.asarray(want.cols[name])), name


def test_probe_counts_interpret_matches_ref():
    rng = np.random.default_rng(4)
    sorted_keys = jnp.sort(jnp.asarray(rng.integers(0, 40, 128), jnp.int32))
    queries = jnp.asarray(rng.integers(0, 40, 96), jnp.int32)
    lo_r, hi_r = fj.probe_counts(queries, sorted_keys, backend="ref")
    lo_p, hi_p = fj.probe_counts_pallas(queries, sorted_keys, block_q=32,
                                        block_r=32, interpret=True)
    assert np.array_equal(np.asarray(lo_r), np.asarray(lo_p))
    assert np.array_equal(np.asarray(hi_r), np.asarray(hi_p))


def test_local_join_fused_impl():
    rng = np.random.default_rng(5)
    left = rel(rng.integers(0, 10, 40))
    right = rel(rng.integers(0, 10, 30), val_name="w")
    outs = {}
    for impl in ("sort_merge", "fused", "all_pairs"):
        o, f = local_join(left, right, "b", "b", 512, impl=impl)
        assert not bool(f)
        outs[impl] = o.to_tuple_set()
    assert outs["sort_merge"] == outs["fused"] == outs["all_pairs"]


@pytest.mark.parametrize("strategy", ["one_round", "cascade"])
def test_executor_fused_matches_staged(strategy):
    from repro.core import (ChainCaps, JoinQuery, execute_query,
                            query_table_inputs)
    rng = np.random.default_rng(6)
    query = JoinQuery.triangle()
    edges = (rng.integers(0, 14, 50).astype(np.int32),
             rng.integers(0, 14, 50).astype(np.int32))
    shape = (2, 2, 2) if strategy == "one_round" else (4,)
    rels = query_table_inputs(query, [edges] * 3, shape)
    grid = SimGrid(shape)
    caps = ChainCaps(recv=512, mid=4096, out=8192, local=1024)
    results = {}
    for impl in ("sort_merge", "fused"):
        out, st, ovf = execute_query(grid, query, rels, strategy=strategy,
                                     caps=caps, join_impl=impl)
        assert not bool(ovf)
        results[impl] = (out.to_tuple_set(query.attrs),
                         {k: np.asarray(v).tolist() for k, v in st.items()})
    assert results["sort_merge"] == results["fused"]


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(n_left=st.integers(0, 40), n_right=st.integers(0, 40),
           dom=st.integers(1, 12), cap_slack=st.integers(0, 16),
           p_valid=st.floats(0.0, 1.0), seed=st.integers(0, 999))
    def test_fused_bit_identical_random(n_left, n_right, dom, cap_slack,
                                        p_valid, seed):
        rng = np.random.default_rng(seed)
        lk = rng.integers(0, dom, n_left)
        rk = rng.integers(0, dom, n_right)
        lv = rng.random(n_left) < p_valid
        rv = rng.random(n_right) < p_valid
        left = rel(lk, capacity=max(1, n_left + cap_slack), valid=lv)
        right = rel(rk, capacity=max(1, n_right + cap_slack), valid=rv,
                    val_name="w")
        matches = int(np.sum(lv[:, None] & rv[None, :]
                             & (lk[:, None] == rk[None, :]))
                      if n_left and n_right else 0)
        # straddle the overflow boundary: below, at, and above
        for out_cap in {max(1, matches - 1), max(1, matches),
                        matches + 4}:
            assert_bit_identical(("random", seed, out_cap), left, right,
                                 out_cap)

    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(1, 200), k=st.integers(1, 32),
           seed=st.integers(0, 999))
    def test_partition_order_random(n, k, seed):
        rng = np.random.default_rng(seed)
        bucket = jnp.asarray(rng.integers(0, k, n), jnp.int32)
        order = fj.partition_order(bucket, k)
        if order is None:
            return
        want = jnp.argsort(bucket, stable=True)
        assert np.array_equal(np.asarray(order), np.asarray(want))
