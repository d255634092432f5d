"""Tuples the shuffle moved per completed query, from the engine's own
exact count (``shuffled`` in its measured statistics)."""


def read(ctx):
    counts = [s for s in ctx["shuffled"] if s is not None]
    return sum(counts) / len(counts) if counts else None
