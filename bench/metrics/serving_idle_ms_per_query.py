"""Device idle milliseconds per completed query whose innermost covering
host span is one of the serving engine's own (``engine.resolve``,
``engine.build``, ``engine.prep``, ``engine.run``, ``engine.result``):
the device waiting on ``QueryEngine.submit``'s host work."""


def read(ctx):
    gaps = ctx["trace"].get("gaps_by_span", {})
    engine = [s for name, s in gaps.items() if name.startswith("engine.")]
    if not engine:
        return None
    return 1e3 * sum(engine) / ctx["queries"]
