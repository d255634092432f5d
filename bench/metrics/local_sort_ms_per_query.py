"""Device milliseconds per completed query of the per-reducer stable
sorts by join key before a merge join (``join.sort``)."""

from . import scopes


def read(ctx):
    return scopes.ms_per_query(ctx, "sort")
