"""Device milliseconds of all-to-all and all-gather operations per
completed query, averaged over the devices: the shuffle across chips."""

from . import ops


def read(ctx):
    return ops.ms_per_query(ctx, ops.COLLECTIVE)
