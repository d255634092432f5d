"""The chunked (overlapped) hop shuffle is invisible to results.

``overlap_chunks=C`` splits each hop's send side into C row blocks so
block b+1's all-to-all can overlap block b's local join.  The schedule
must change *nothing observable*: same output tuples, same overflow
flag, bit-equal accounting stats (the Shares/cascade accounting is
per-tuple, and chunking moves the same tuples).  Only the live-row
counter differs: the chunked schedule fills a receive buffer and a join
output per chunk, so it holds at least the staged schedule's buffer and
live rows.  These tests pin that across every
executor entry point on SimGrid; ``tests/_query_shard_check.py`` pins
the same equality (plus the collective structure of the lowering) on a
real multi-device ShardGrid.

Also pins the cost-model overlap envelope: ``hop_time_overlapped`` at
C=1 equals the staged time, never exceeds it, and
``overlap_hidden_fraction`` handles the degenerate zero-shuffle case.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (ChainCaps, ChainQuery, JoinQuery, Relation, SimGrid,
                        cascade_chain, chain_edge_inputs, execute_query,
                        query_table_inputs, two_way_join)
from repro.core.cost_model import (hop_time_overlapped, hop_time_staged,
                                   overlap_hidden_fraction)
from repro.core.shuffle import FILL_KEYS, concat_rows, split_rows

CHUNK_COUNTS = (2, 3, 5)


def edges(rng, dom, m):
    return (rng.integers(0, dom, m).astype(np.int32),
            rng.integers(0, dom, m).astype(np.int32))


def run_and_snapshot(fn, chunks):
    out, st, ovf = fn(chunks)
    return (out.to_tuple_set(), int(np.sum(np.asarray(out.valid))),
            bool(ovf), {k: np.asarray(v) for k, v in st.items()})


def assert_overlap_invisible(fn, *, expect_overflow=False):
    """fn(chunks) -> (out, stats, ovf); every chunking must match C=1."""
    base_set, base_n, base_ovf, base_st = run_and_snapshot(fn, 1)
    assert base_ovf == expect_overflow
    for c in CHUNK_COUNTS:
        got_set, got_n, got_ovf, got_st = run_and_snapshot(fn, c)
        assert got_ovf == base_ovf, c
        assert sorted(got_st) == sorted(base_st), c
        for k in base_st:
            if k in FILL_KEYS:
                assert got_st[k] >= base_st[k], (c, k)
            else:
                assert np.array_equal(got_st[k], base_st[k]), (c, k)
        # Under overflow only the flag and the accounting are
        # schedule-invariant: truncation hits *pre-filter* matches
        # (cycle-closing predicates filter after the capacity cut), so
        # the schedules can retain different survivor subsets.
        if not expect_overflow:
            assert got_n == base_n, c
            assert got_set == base_set, c


def test_two_way_join_overlap():
    rng = np.random.default_rng(0)
    grid = SimGrid((4,))
    q2 = ChainQuery.chain(2)
    left, right = chain_edge_inputs(
        q2, [edges(rng, 12, 40), edges(rng, 12, 40)], (4,))

    def fn(chunks):
        return two_way_join(grid, left, right, "b", "b",
                            recv_capacity=256, out_capacity=2048,
                            overlap_chunks=chunks)

    assert_overlap_invisible(fn)


def test_cascade_chain_pushdown_overlap():
    rng = np.random.default_rng(1)
    query = ChainQuery.chain(3, aggregate=True)
    rels = chain_edge_inputs(query, [edges(rng, 16, 48) for _ in range(3)],
                             (4,))
    grid = SimGrid((4,))
    caps = ChainCaps(recv=512, mid=2048, out=4096, local=1024, agg=1024)

    def fn(chunks):
        return cascade_chain(grid, query, rels, caps=caps, pushdown=True,
                             measure_skew=True, overlap_chunks=chunks)

    assert_overlap_invisible(fn)


@pytest.mark.parametrize("strategy,shape", [("one_round", (2, 2, 2)),
                                            ("cascade", (4,))])
def test_triangle_overlap(strategy, shape):
    rng = np.random.default_rng(2)
    query = JoinQuery.triangle()
    rels = query_table_inputs(query, [edges(rng, 14, 48)] * 3, shape)
    grid = SimGrid(shape)
    caps = ChainCaps(recv=512, mid=4096, out=8192, local=1024)

    def fn(chunks):
        return execute_query(grid, query, rels, strategy=strategy,
                             caps=caps, overlap_chunks=chunks)

    assert_overlap_invisible(fn)


@pytest.mark.parametrize("strategy,shape", [("one_round", (2, 2, 2)),
                                            ("cascade", (4,))])
def test_triangle_overlap_tiny_out_overflow(strategy, shape):
    # out=8 is far below the triangle count: the shared final
    # compaction must raise the same overflow under every chunking.
    rng = np.random.default_rng(3)
    query = JoinQuery.triangle()
    rels = query_table_inputs(query, [edges(rng, 8, 64)] * 3, shape)
    grid = SimGrid(shape)
    caps = ChainCaps(recv=512, mid=4096, out=8, local=1024)

    def fn(chunks):
        return execute_query(grid, query, rels, strategy=strategy,
                             caps=caps, overlap_chunks=chunks)

    assert_overlap_invisible(fn, expect_overflow=True)


def test_star_one_round_overlap():
    rng = np.random.default_rng(4)
    query = JoinQuery.star(3)
    rels = query_table_inputs(query, [edges(rng, 10, 40)] * 3, (4,))
    grid = SimGrid((4,))
    caps = ChainCaps(recv=512, mid=4096, out=8192, local=1024)

    def fn(chunks):
        return execute_query(grid, query, rels, strategy="one_round",
                             caps=caps, overlap_chunks=chunks)

    assert_overlap_invisible(fn)


def test_split_concat_rows_partition_rows_exactly():
    rng = np.random.default_rng(5)
    cols = {"b": jnp.asarray(rng.integers(0, 9, 37), jnp.int32),
            "v": jnp.asarray(rng.random(37), jnp.float32)}
    valid = jnp.asarray(rng.random(37) < 0.6)
    rel = Relation(cols, valid)
    for chunks in (1, 2, 3, 5, 37, 100):
        parts = split_rows(rel, chunks)
        assert len(parts) == min(max(1, chunks), rel.capacity)
        assert sum(p.capacity for p in parts) == rel.capacity
        assert sum(int(jnp.sum(p.valid)) for p in parts) \
            == int(jnp.sum(rel.valid))
        merged = concat_rows(parts)
        assert np.array_equal(np.asarray(merged.valid), np.asarray(valid))
        for n in cols:
            assert np.array_equal(np.asarray(merged.cols[n]),
                                  np.asarray(cols[n]))


def test_hop_time_model():
    # C=1 degenerates to the staged time exactly
    assert hop_time_overlapped(3.0, 5.0, 1) == hop_time_staged(3.0, 5.0)
    # never exceeds staged; non-increasing in C when both phases run
    prev = hop_time_staged(4.0, 6.0)
    for c in (1, 2, 3, 4, 8, 16):
        t = hop_time_overlapped(4.0, 6.0, c)
        assert t <= prev + 1e-12, c
        prev = t
    # C→∞ limit: the longer phase
    assert abs(hop_time_overlapped(4.0, 6.0, 10 ** 6) - 6.0) < 1e-3
    # fully compute-bound hiding: fraction → 1 as C grows
    frac = overlap_hidden_fraction(hop_time_staged(4.0, 6.0),
                                   hop_time_overlapped(4.0, 6.0, 8),
                                   4.0)
    assert 0.8 < frac <= 1.0
    # degenerate zero-shuffle hop
    assert overlap_hidden_fraction(5.0, 5.0, 0.0) == 0.0
    assert overlap_hidden_fraction(5.0, 5.0, -1.0) == 0.0
