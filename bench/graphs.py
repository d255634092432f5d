"""R-MAT edge lists for the benchmark, and the seed's relabelling.

``rmat_edges`` is a copy of the program's generator
(``src/repro/data/graphs.py``), kept here so that later changes to the
program cannot change the benchmark's data.  It draws a directed,
deduplicated R-MAT edge list with quadrant probabilities
``a, b = c = 0.4 (1 - a), d = 0.2 (1 - a)`` over ``2**scale`` nodes and
``edge_factor * 2**scale`` draws.

``query_tables`` turns one structure into the tables a client submits:
the structure is fixed by the configuration (``structure_seed``), and
the run's ``--seed`` draws, for each variant, a permutation of the node
ids and an order of the edges.  Every seed therefore asks for the same
amount of work (the same path and group counts, the same buffer sizes),
while the ids, and so the reducers every key hashes to, differ.
"""

from __future__ import annotations

import numpy as np


def rmat_edges(scale: int, edge_factor: float, a: float,
               seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Directed R-MAT edge list (src, dst) as int32, deduplicated."""
    rng = np.random.default_rng(seed)
    m = int((1 << scale) * edge_factor)
    rem = 1.0 - a
    b, c = rem * 0.4, rem * 0.4
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        r = rng.random(m)
        go_src = r >= a + b                                 # bottom half
        go_dst = ((r >= a) & (r < a + b)) | (r >= a + b + c)  # right half
        src |= go_src.astype(np.int64) << bit
        dst |= go_dst.astype(np.int64) << bit
    edges = np.unique(np.stack([src, dst], axis=1), axis=0)
    perm = rng.permutation(1 << scale)
    return (perm[edges[:, 0]].astype(np.int32),
            perm[edges[:, 1]].astype(np.int32))


def query_tables(graph: dict, seed: int, variants: int
                 ) -> list[tuple[np.ndarray, np.ndarray]]:
    """``variants`` relabelled copies of the configuration's graph, drawn
    from ``seed``: one (src, dst) edge list per variant."""
    src, dst = rmat_edges(graph["scale"], graph["edge_factor"], graph["a"],
                          graph["structure_seed"])
    n = 1 << graph["scale"]
    out = []
    for v in range(variants):
        rng = np.random.default_rng([seed, v])
        ids = rng.permutation(n).astype(np.int32)
        order = rng.permutation(len(src))
        out.append((ids[src[order]], ids[dst[order]]))
    return out
