"""Device milliseconds per completed query of the expansion of matches
into output slots and the gather of output columns (``join.emit``)."""

from . import scopes


def read(ctx):
    return scopes.ms_per_query(ctx, "emit")
