"""How device operations are told apart in a v5e trace, for the readers
of device time.  ``tracing.load`` gives each operation its kind: the HLO
opcode, or a custom call's target.  A Pallas kernel is a custom call to
``tpu_custom_call``; the trace names it ``closed_call.N``, not by the
kernel's own name, so every Mosaic kernel of the program counts."""

SORT = ("sort",)
PALLAS = ("tpu_custom_call",)
COLLECTIVE = ("all-to-all", "all-gather", "all-gather-start",
              "all-gather-done", "all-reduce", "all-reduce-start",
              "all-reduce-done", "collective-permute",
              "collective-permute-start", "collective-permute-done")


def ms_per_query(ctx, kinds):
    """Milliseconds of device time of the given kinds per query, or None
    where the trace holds none of them."""
    hits = [s for k, s in ctx["trace"]["kind_s"].items() if k in kinds]
    if not hits:
        return None
    return 1e3 * sum(hits) / ctx["queries"]
