"""Device time of one ``join.*`` scope per completed query, for the
readers of the engine's layers: ``trace["scope_s"]`` of
``bench/layers.py``, the self time of every operation whose innermost
``join.*`` name-stack component is the scope, mean over the devices."""


def ms_per_query(ctx, scope):
    """Milliseconds per query, or None where the trace holds no
    operation of the scope (or no scopes at all)."""
    seconds = ctx["trace"].get("scope_s", {}).get(scope)
    if not seconds:
        return None
    return 1e3 * seconds / ctx["queries"]
