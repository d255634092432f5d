"""Device milliseconds of the engine's Pallas kernels (``hash_histogram``,
``segment_sum``, ``probe_counts``) per completed query: every custom
call to ``tpu_custom_call``, since the trace does not carry a kernel's
own name."""

from . import ops


def read(ctx):
    return ops.ms_per_query(ctx, ops.PALLAS)
