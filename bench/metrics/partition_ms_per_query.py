"""Device milliseconds per completed query of the map-side bucket
hashing, bucket histograms and the counting-sort partition into send
buffers (``join.partition``)."""

from . import scopes


def read(ctx):
    return scopes.ms_per_query(ctx, "partition")
