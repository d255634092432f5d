"""Entry through the serving front end: ``QueryEngine.submit``.

One engine holds the plan-and-executable cache; every query hands it
the client's edge lists and statistics.  A query ends when its output
columns are on the host as numpy arrays.  ``CONTROL``, the control of
``bench/control.py``, cuts the buffer slack to 1 and turns off the
engine's rounding of capacities to powers of two.
"""

from __future__ import annotations

import jax

from .common import output_columns


class Entry:
    CONTROL = {"caps_slack": 1, "round_caps": False, "keep_overflow": True}

    def __init__(self, query, config: dict, devices, caps_slack=None,
                 round_caps=True, keep_overflow=False):
        from repro.serving import QueryEngine, QueryServeConfig
        self.query = query
        self.columns = output_columns(query)
        self.engine = QueryEngine(QueryServeConfig(
            k=config["reducers"],
            caps_slack=caps_slack or config["caps_slack"],
            quantize_caps=round_caps))
        if keep_overflow:
            lane = self.engine._lane_result
            self.engine._lane_result = (
                lambda entry, out, st, ovf, *a, **kw:
                lane(entry, out, st, False, *a, **kw))

    def submit(self, tables, stats) -> dict:
        """One query: submit, then fetch the output to the host."""
        with jax.profiler.TraceAnnotation("bench.submit"):
            res = self.engine.submit(
                self.query, [tables] * self.query.n_relations, stats=stats)
        out = None
        if res.ok:
            with jax.profiler.TraceAnnotation("bench.fetch"):
                out = jax.device_get(
                    ({c: res.output.cols[c] for c in self.columns},
                     res.output.valid))
        return {"ok": res.ok and not res.overflow, "output": out,
                "shuffled": (res.measured or {}).get("shuffled"),
                "plan": (res.plan.strategy, tuple(res.plan.grid_shape))
                if res.plan is not None else None,
                "error": res.error}

    def close(self) -> None:
        self.engine = None

