"""Device milliseconds of HLO sort operations per completed query (the
local sort-merge join, partitioning and group-by of ``core/local.py``)."""

from . import ops


def read(ctx):
    return ops.ms_per_query(ctx, ops.SORT)
