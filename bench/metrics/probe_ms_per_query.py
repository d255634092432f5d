"""Device milliseconds per completed query of the match count of the
sort-merge join, the run bounds of every left key in the sorted right
side (``join.probe``)."""

from . import scopes


def read(ctx):
    return scopes.ms_per_query(ctx, "probe")
