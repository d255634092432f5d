"""Plain numpy reference for the three-way chain R(a,b) ⋈ R(b,c) ⋈ R(c,d),
and the comparison that decides ``correct``.

It imports nothing of the program.  The join is a bag join: every
directed walk a→b→c→d over the edge list is one result tuple, repeated
nodes included.  The aggregated query groups those tuples by (a, d) and
counts them (the product of unit edge values, summed).
"""

from __future__ import annotations

import numpy as np


def _extend(paths: np.ndarray, src: np.ndarray, dst: np.ndarray
            ) -> np.ndarray:
    """Append one hop to every path: rows (…, x) become (…, x, y) for
    each edge x→y."""
    order = np.argsort(src, kind="stable")
    s, d = src[order], dst[order]
    last = paths[:, -1]
    lo = np.searchsorted(s, last, side="left")
    hi = np.searchsorted(s, last, side="right")
    reps = hi - lo
    rows = np.repeat(np.arange(len(paths)), reps)
    offs = np.arange(len(rows)) - np.repeat(np.cumsum(reps) - reps, reps)
    nxt = d[np.repeat(lo, reps) + offs]
    return np.concatenate([paths[rows], nxt[:, None]], axis=1)


def chain_paths(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Every 3-path (a, b, c, d) as an int64 (P, 4) array, sorted."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    paths = np.stack([src, dst], axis=1)
    paths = _extend(_extend(paths, src, dst), src, dst)
    return paths[np.lexsort(paths.T[::-1])]


def path_counts(paths: np.ndarray) -> np.ndarray:
    """(a, d, number of 3-paths from a to d) as a float64 (G, 3) array,
    sorted by (a, d)."""
    ad, counts = np.unique(paths[:, [0, 3]], axis=0, return_counts=True)
    return np.concatenate([ad.astype(np.float64),
                           counts[:, None].astype(np.float64)], axis=1)


def answer(src, dst, aggregate: bool) -> np.ndarray:
    """The query's full answer: sorted rows, (a, b, c, d) or (a, d, n)."""
    paths = chain_paths(src, dst)
    return path_counts(paths) if aggregate else paths


def path_total(answer_rows: np.ndarray, aggregate: bool) -> float:
    """The number of 3-paths an answer stands for."""
    return float(answer_rows[:, 2].sum() if aggregate else len(answer_rows))


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
    """Largest |count - reference count| over the (a, d) groups of either
    answer; a group missing from one side counts its whole count."""
    if len(got) == 0 and len(want) == 0:
        return 0.0
    keys = np.concatenate([got[:, :2], want[:, :2]])
    vals = np.concatenate([got[:, 2], -want[:, 2]])
    _, inv = np.unique(keys, axis=0, return_inverse=True)
    net = np.zeros(inv.max() + 1, np.float64)
    np.add.at(net, inv.ravel(), vals)
    return float(np.abs(net).max())
