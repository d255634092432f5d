"""Live rows over buffer rows, in percent, over every completed query:
the program's own exact counts (``live_rows`` and ``buffer_rows`` in
its measured statistics) of the valid rows and the static capacity of
every buffer a query fills."""


def read(ctx):
    measured = [m for m in ctx.get("measured") or [] if m
                and "live_rows" in m]
    if not measured:
        return None
    return 100.0 * (sum(m["live_rows"] for m in measured)
                    / sum(m["buffer_rows"] for m in measured))
