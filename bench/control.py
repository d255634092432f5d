#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in
one process on the chip.

    python3 bench/control.py --workload amazon_k16.agg --seeds 3 --seconds 3

Every reading is one ``harness.run`` of the cell, with its set-up, its
window and its own comparison with the reference, and one JSON line of
the run's ``correct`` and ``checks``:

- ``program``: the cell as it is timed;
- ``truncate``: the program with every buffer as tight as the client's
  statistics give it and its overflow flag ignored (the entry's
  ``CONTROL``), the step a change that trims padding would take: a
  truncated answer comes back as if it were whole, which breaks the
  configuration's guarantee that every tuple is returned;
- ``bf16`` (aggregated cells): the reference in the program's place,
  its path counts computed in bfloat16 on the device, the precision
  below the configuration's float32 counts.

``--kinds`` chooses among them; the program's own readings come from the
timed runs too.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


class Bf16Reference:
    """The reference in the program's place: (a, d, count) of the
    3-paths, the counts computed on the device as bfloat16 products of
    the adjacency matrix."""

    def __init__(self, query, config: dict, devices):
        import jax
        from entries.common import output_columns
        self.columns = output_columns(query)
        self.device = devices[0]
        self.n = 1 << config["graph"]["scale"]
        self.counts = jax.jit(bf16_counts, static_argnums=2)

    def submit(self, tables, stats) -> dict:
        import jax
        import numpy as np
        src, dst = jax.device_put(tuple(np.asarray(c, np.int32)
                                        for c in tables), self.device)
        a3 = np.asarray(self.counts(src, dst, self.n), np.float64)
        a, d = np.nonzero(a3)
        out = (dict(zip(self.columns, (a, d, a3[a, d]))),
               np.ones(len(a), bool))
        return {"ok": True, "output": out, "shuffled": None,
                "plan": "bf16 reference", "error": None}

    def close(self) -> None:
        pass


def bf16_counts(src, dst, n: int):
    """Paths of three hops from every a to every d, as a float32 (n, n)
    array of bfloat16 counts: each product of bfloat16 matrices is
    rounded to bfloat16 (``reduce_precision``, which XLA may not fold
    away as it may a round trip through ``astype``)."""
    import jax.numpy as jnp
    from jax import lax

    def bf16(x):
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    adj = jnp.zeros((n, n), jnp.float32).at[src, dst].set(1)
    a2 = bf16(jnp.matmul(adj, adj, precision="highest"))
    return bf16(jnp.matmul(a2, adj, precision="highest"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--kinds", nargs="+", default=["truncate", "bf16"],
                    choices=["program", "truncate", "bf16"])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import harness

    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()
    devices = harness.require_chip(cell["chips"])
    cls = harness.entry_class(cell["config_data"]["entry"])
    kinds = {"program": cls, "truncate": functools.partial(cls, **cls.CONTROL)}
    if cell["traffic_data"]["aggregate"]:
        kinds["bf16"] = Bf16Reference
    for kind in [k for k in args.kinds if k in kinds]:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            r = harness.run(cell, seed, args.seconds, False, devices=devices,
                            entry_cls=kinds[kind])
            print(json.dumps({"workload": args.workload, "kind": kind,
                              "seed": seed, "correct": r["correct"],
                              "attempted": r["attempted"],
                              "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
