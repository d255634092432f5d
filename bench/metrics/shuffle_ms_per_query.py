"""Device milliseconds per completed query of the exchange of send
buffers between reducers and the compaction of what arrives: the lane
exchange on a SimGrid, the collectives on a ShardGrid (``join.shuffle``)."""

from . import scopes


def read(ctx):
    return scopes.ms_per_query(ctx, "shuffle")
