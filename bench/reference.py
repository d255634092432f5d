"""Plain numpy reference for a join of binary relations over one edge
list, and the comparison that decides ``correct``.

It imports nothing of the program.  The query's structure comes as plain
data (``Structure``): its attribute names, each relation's attribute
pair and the aggregate's group keys; the default is the paper's chain
R(a,b) ⋈ R(b,c) ⋈ R(c,d).  Every relation is the same edge list.  The
join is a bag join: every assignment of nodes to the attributes under
which each relation's pair is an edge is one result tuple, repeated
nodes included (for the chain, every directed walk a→b→c→d; for
``cycle3``, every closed walk a→b→c→a).  The aggregated query groups
those tuples by its keys and counts them (the product of unit edge
values, summed).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Structure(NamedTuple):
    """A query's shape: attribute names in output order, one attribute
    pair per relation, and the aggregate's group keys."""
    attrs: tuple
    relations: tuple
    keys: tuple = ()


CHAIN3 = Structure(attrs=("a", "b", "c", "d"),
                   relations=(("a", "b"), ("b", "c"), ("c", "d")),
                   keys=("a", "d"))


def _follow(keys: np.ndarray, src: np.ndarray, dst: np.ndarray):
    """Every edge out of every key: (index into ``keys``, the edge's
    other end) for each edge key→y."""
    order = np.argsort(src, kind="stable")
    s, d = src[order], dst[order]
    lo = np.searchsorted(s, keys, side="left")
    hi = np.searchsorted(s, keys, side="right")
    reps = hi - lo
    rows = np.repeat(np.arange(len(keys)), reps)
    offs = np.arange(len(rows)) - np.repeat(np.cumsum(reps) - reps, reps)
    return rows, d[np.repeat(lo, reps) + offs]


def _pair(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """One int64 per (x, y) pair of int32 ids, distinct pairs distinct."""
    return (x << 32) | (y & 0xFFFFFFFF)


def _edge_counts(xs, ys, src, dst) -> np.ndarray:
    """How often each (xs[i], ys[i]) is an edge of the list."""
    edges = np.sort(_pair(src, dst))
    keys = _pair(xs, ys)
    return (np.searchsorted(edges, keys, side="right")
            - np.searchsorted(edges, keys, side="left"))


def join(src, dst, query: Structure = CHAIN3) -> dict:
    """Every result tuple as one int64 column per attribute, in no set
    order: relation by relation, in their order, from the first
    attribute, which an earlier relation binds; a free second attribute
    extends each partial tuple by every edge, and a bound one keeps each
    tuple as often as its pair is an edge."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    x, y = query.relations[0]
    cols = {x: src, y: dst}
    for x, y in query.relations[1:]:
        if y in cols:
            counts = _edge_counts(cols[x], cols[y], src, dst)
            rows = np.repeat(np.arange(len(counts)), counts)
            cols = {a: c[rows] for a, c in cols.items()}
        else:
            rows, nxt = _follow(cols[x], src, dst)
            cols = {a: c[rows] for a, c in cols.items()}
            cols[y] = nxt
    return cols


def _rows(cols: dict, names) -> np.ndarray:
    return np.stack([cols[a] for a in names], axis=1)


def answer(src, dst, aggregate: bool, query: Structure = CHAIN3
           ) -> np.ndarray:
    """The query's full answer as sorted rows: every tuple, one int64
    column per attribute in ``query.attrs`` order ((a, b, c, d) for the
    chain), or, aggregated, the float64 (*keys, count) of every group
    ((a, d, n) for the chain)."""
    cols = join(src, dst, query)
    if aggregate:
        keys, counts = np.unique(_rows(cols, query.keys), axis=0,
                                 return_counts=True)
        return np.concatenate([keys.astype(np.float64),
                               counts[:, None].astype(np.float64)], axis=1)
    tuples = _rows(cols, query.attrs)
    return tuples[np.lexsort(tuples.T[::-1])]


def path_total(answer_rows: np.ndarray, aggregate: bool) -> float:
    """The number of join result tuples an answer stands for: the sum of
    the count column (the last) of an aggregate, the rows otherwise."""
    return float(answer_rows[:, -1].sum() if aggregate
                 else len(answer_rows))


def _sorted(rows: np.ndarray) -> np.ndarray:
    return rows[np.lexsort(rows.T[::-1])] if len(rows) else rows


def mismatched_rows(got: np.ndarray, want: np.ndarray) -> int:
    """Rows in one multiset and not in the other (the size of the
    symmetric difference): 0 exactly when the answers agree."""
    got, want = _sorted(np.asarray(got, np.float64)), _sorted(want)
    if got.shape == want.shape and np.array_equal(got, want):
        return 0
    both = np.concatenate([got, want])
    side = np.concatenate([np.ones(len(got), np.int64),
                           -np.ones(len(want), np.int64)])
    _, inv = np.unique(both, axis=0, return_inverse=True)
    net = np.zeros(inv.max() + 1 if len(inv) else 0, np.int64)
    np.add.at(net, inv.ravel(), side)
    return int(np.abs(net).sum())


def max_count_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |count - reference count| over the groups of either
    aggregated answer (every column but the last is the group key, the
    last the count); a group missing from one side counts its whole
    count."""
    if len(got) == 0 and len(want) == 0:
        return 0.0
    keys = np.concatenate([got[:, :-1], want[:, :-1]])
    vals = np.concatenate([got[:, -1], -want[:, -1]])
    _, inv = np.unique(keys, axis=0, return_inverse=True)
    net = np.zeros(inv.max() + 1, np.float64)
    np.add.at(net, inv.ravel(), vals)
    return float(np.abs(net).max())
