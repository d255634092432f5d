"""Device milliseconds per completed query of the group-by of an
aggregated query: its sort, ``segment_sum`` and output (``join.groupby``)."""

from . import scopes


def read(ctx):
    return scopes.ms_per_query(ctx, "groupby")
