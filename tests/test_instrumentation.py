"""The program's own instrumentation on the CPU: the live-row counter
(``live_rows`` and ``buffer_rows`` in every lowering's stats) against
the answers and against the buffers a one-round plan fills, and the
serving engine's ``engine.*`` host spans in a profiler trace."""

import glob
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.core import (JoinQuery, SimGrid, default_query_caps,
                        execute_query, query_stats_exact,
                        query_table_inputs)
from repro.serving import QueryEngine, QueryServeConfig

SEEDS = (0, 1, 2**31 + 5)


def graph(seed, nodes=40, edges=120):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, nodes, edges).astype(np.int32),
            rng.integers(0, nodes, edges).astype(np.int32))


def paths(src, dst, n=40):
    """(2-paths, 3-paths, (a, d) groups) of the edge multiset."""
    adj = np.zeros((n, n), np.int64)
    np.add.at(adj, (src, dst), 1)
    a2 = adj @ adj
    a3 = a2 @ adj
    return int(a2.sum()), int(a3.sum()), int(np.count_nonzero(a3))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("aggregate", [False, True])
def test_output_buffer_holds_the_answer(seed, aggregate):
    """Through ``QueryEngine.submit``: the output buffer's live rows are
    the 3-paths (the (a, d) groups when aggregated), and the counter's
    totals are plain floats beside the engine's other counters."""
    src, dst = graph(seed)
    query = JoinQuery.chain(3, aggregate=aggregate)
    res = QueryEngine(QueryServeConfig(k=16, caps_slack=8)).submit(
        query, [(src, dst)] * 3)
    assert res.ok
    _, p3, groups = paths(src, dst)
    assert int(np.sum(np.asarray(res.output.valid))) == (
        groups if aggregate else p3)
    m = res.measured
    assert all(type(v) is float for v in m.values())
    assert p3 <= m["live_rows"] < m["buffer_rows"]


def test_live_rows_follow_the_work_not_the_labels():
    """Relabelled node ids ask for the same work: the same live and
    buffer rows, and so the same share."""
    src, dst = graph(3)
    perm = np.random.default_rng(9).permutation(40).astype(np.int32)
    query = JoinQuery.chain(3)
    eng = QueryEngine(QueryServeConfig(k=16, caps_slack=8))
    stats = query_stats_exact(query, [(src, dst)] * 3)
    a = eng.submit(query, [(src, dst)] * 3, stats=stats)
    b = eng.submit(query, [(perm[src], perm[dst])] * 3, stats=stats)
    assert a.ok and b.ok and b.cache_hit
    assert (a.measured["live_rows"], a.measured["buffer_rows"]) == \
        (b.measured["live_rows"], b.measured["buffer_rows"])


def one_round_fill(m, p2, p3, caps, k0, k1):
    """The buffers the one-round 3-chain fills on a (k0, k1) grid: each
    relation's shuffle hops (the K·recv receive buffer, and the local
    buffer where ``local`` is smaller) and broadcasts (the gathered
    buffer and its local compaction), then the two local joins' outputs.
    (live rows, buffer rows per device)."""
    live, rows = 0, 0

    def hop(k, n_live):
        nonlocal live, rows
        bufs = [k * caps.recv]
        if caps.local < k * caps.recv:
            bufs.append(caps.local)
        live += n_live * len(bufs)
        rows += sum(bufs)
        return bufs[-1]

    def bcast(k, cap_in, n_live):
        nonlocal live, rows
        live += 2 * n_live
        rows += k * cap_in + caps.local

    bcast(k1, hop(k0, m), m * k1)          # R(a, b): hash b, copy along c
    hop(k0, m)                             # S(b, c): hash b, then c
    hop(k1, m)
    bcast(k0, hop(k1, m), m * k0)          # T(c, d): hash c, copy along b
    live += p2 + p3                        # the two joins' outputs
    rows += caps.mid + caps.out
    return live, rows


@pytest.mark.parametrize("seed", SEEDS)
def test_one_round_counts_every_buffer(seed):
    src, dst = graph(seed)
    query = JoinQuery.chain(3)
    shape = (4, 4)
    stats = query_stats_exact(query, [(src, dst)] * 3)
    caps = default_query_caps(query, stats, shape, slack=8)
    rels = query_table_inputs(query, [(src, dst)] * 3, shape)
    _, st, ovf = execute_query(SimGrid(shape), query, rels,
                               strategy="one_round", caps=caps)
    assert not bool(ovf)
    p2, p3, _ = paths(src, dst)
    live, rows = one_round_fill(len(src), p2, p3, caps, *shape)
    assert float(st["live_rows"]) == live
    assert float(st["buffer_rows"]) == rows * 16


def host_spans(fn):
    """[(name, start_ns, end_ns, query)] of the ``engine.*`` spans that
    ``fn`` leaves in a profiler trace."""
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        fn()
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        data = ProfileData.from_file(path)
        return sorted((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                       int(dict(ev.stats)["query"]))
                      for plane in data.planes
                      if plane.name.startswith("/host:")
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith("engine."))


def test_engine_spans_share_the_query_id():
    """A miss builds inside its resolve; a hit resolves without a
    build; each query's spans carry the engine's query count."""
    src, dst = graph(4)
    query = JoinQuery.chain(3, aggregate=True)
    eng = QueryEngine(QueryServeConfig(k=4))
    stats = query_stats_exact(query, [(src, dst)] * 3)
    spans = host_spans(lambda: [eng.submit(query, [(src, dst)] * 3,
                                           stats=stats) for _ in range(2)])
    by_query = {}
    for name, s, e, q in spans:
        by_query.setdefault(q, {})[name] = (s, e)
    assert set(by_query) == {0, 1}
    steps = ["engine.resolve", "engine.prep", "engine.run", "engine.result"]
    assert set(by_query[0]) == set(steps) | {"engine.build"}
    assert set(by_query[1]) == set(steps)
    (rs, re_), (bs, be) = by_query[0]["engine.resolve"], \
        by_query[0]["engine.build"]
    assert rs <= bs and be <= re_
    for q in (0, 1):
        ends = [by_query[q][n] for n in steps]
        assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))
