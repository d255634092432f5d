"""What every entry shares: the output columns of a query, and the rows
of a fetched output."""

from __future__ import annotations

import numpy as np


def output_columns(query) -> tuple:
    """(*keys, count) for an aggregated query, every attribute otherwise:
    (a, d, count) and (a, b, c, d) for the chain."""
    if query.aggregate is not None:
        return tuple(query.aggregate.keys) + (query.aggregate.out,)
    return tuple(query.attrs)


def rows(out, columns) -> np.ndarray:
    """Valid rows of a fetched ``(columns dict, valid)`` output as a
    float64 (N, len(columns)) array."""
    cols, valid = out
    valid = np.asarray(valid).ravel()
    return np.stack([np.asarray(cols[c]).ravel()[valid].astype(np.float64)
                     for c in columns], axis=1)
