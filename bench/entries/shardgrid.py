"""Entry across chips: ``ShardGrid(mesh, axes).run`` over
``execute_query``, one reducer per chip.

The plan is the planner's at ``k = reducers``; every buffer capacity is
derived from the client's statistics with the configuration's slack and
rounded up to a power of two, as the serving engine does, so that every
seed shares one executable.  ``CONTROL``, the control of
``bench/control.py``, takes the slack away and the rounding with it,
and ignores the overflow flag, so that a truncated answer comes back as
if it were whole.
Inputs are dealt round-robin to the chips on the host and placed with
one ``device_put`` per query; the output of every chip is fetched to
the host.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from .common import output_columns


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


class Entry:
    CONTROL = {"caps_slack": 1, "round_caps": False, "keep_overflow": True}

    def __init__(self, query, config: dict, devices, caps_slack=None,
                 round_caps=True, keep_overflow=False):
        self.query = query
        self.k = config["reducers"]
        self.slack = caps_slack or config["caps_slack"]
        self.round_caps = round_caps
        self.keep_overflow = keep_overflow
        self.devices = list(devices)[:self.k]
        if len(self.devices) != self.k:
            raise RuntimeError(f"{self.k} reducers need {self.k} devices, "
                               f"found {len(self.devices)}")
        self.columns = output_columns(query)
        self._plans = {}     # id(stats) -> program key
        self._programs = {}  # (strategy, shape, caps) -> compiled program

    def _program(self, stats) -> dict:
        key = self._plans.get(id(stats))
        if key is None:
            key = self._plans[id(stats)] = self._plan(stats)
        if key not in self._programs:
            self._programs[key] = self._build(*key)
        return self._programs[key]

    def _plan(self, stats) -> tuple:
        from repro.core import default_query_caps, plan_query
        plan = plan_query(self.query, stats, self.k)
        shape = tuple(plan.grid_shape) if plan.strategy == "one_round" \
            else (self.k,)
        caps = default_query_caps(self.query, stats, shape, slack=self.slack)
        if self.round_caps:
            caps = dataclasses.replace(caps, **{
                f: _pow2(getattr(caps, f)) for f in
                ("recv", "mid", "out", "local", "agg", "join")
                if getattr(caps, f) is not None})
        return plan.strategy, shape, caps, tuple(plan.join_order)

    def _build(self, strategy, shape, caps, order) -> dict:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core import ShardGrid, execute_query
        axes = ("x", "y", "z")[:len(shape)]
        mesh = Mesh(np.array(self.devices).reshape(shape), axes)
        lead = len(shape)
        query = self.query

        def body(grid, *shards):
            flat = [jax.tree.map(lambda a: a.reshape(a.shape[lead:]), r)
                    for r in shards]
            out, st, ovf = execute_query(grid, query, flat, strategy=strategy,
                                         caps=caps, join_order=order)
            out = jax.tree.map(lambda a: a.reshape((1,) * lead + a.shape),
                               out)
            return out, st["shuffled"], grid.reduce_any(ovf)

        spec = P(*axes, None)
        run = jax.jit(lambda *r: ShardGrid(mesh, axes).run(
            body, *r, in_specs=tuple(spec for _ in r),
            out_specs=(spec, P(), P())))
        return dict(shape=shape, run=run, sharding=NamedSharding(mesh, spec),
                    plan=(strategy, shape))

    def _inputs(self, prog, tables):
        """Relations named by the query schema, dealt round-robin to the
        grid on the host: every column becomes (*shape, rows per chip)."""
        from repro.core.relation import Relation
        shape = prog["shape"]
        src, dst = (np.asarray(c, np.int32) for c in tables)
        per = -(-len(src) // self.k)
        pad = per * self.k - len(src)

        def deal(a):
            return np.pad(a, (0, pad)).reshape(shape + (per,))

        valid = deal(np.ones(len(src), bool))
        rels = []
        for j in range(self.query.n_relations):
            a, b, v = self.query.schema(j)
            rels.append(Relation({a: deal(src), b: deal(dst),
                                  v: deal(np.ones(len(src), np.float32))},
                                 valid))
        return jax.device_put(tuple(rels), prog["sharding"])

    def submit(self, tables, stats) -> dict:
        prog = self._program(stats)
        with jax.profiler.TraceAnnotation("bench.prep"):
            rels = self._inputs(prog, tables)
        with jax.profiler.TraceAnnotation("bench.submit"):
            out, shuffled, ovf = prog["run"](*rels)
            jax.block_until_ready(out.valid)
        with jax.profiler.TraceAnnotation("bench.fetch"):
            host, shuffled, ovf = jax.device_get(
                (({c: out.cols[c] for c in self.columns}, out.valid),
                 shuffled, ovf))
        ok = self.keep_overflow or not bool(ovf)
        return {"ok": ok, "output": host if ok else None,
                "shuffled": float(shuffled), "plan": prog["plan"],
                "error": None if ok else "overflow"}

    def close(self) -> None:
        self._programs.clear()

