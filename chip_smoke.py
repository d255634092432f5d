#!/usr/bin/env python3
"""Run the join engine's main path once on a TPU and check its answers.

  python chip_smoke.py              # one chip: batch and served phases
  python chip_smoke.py --chips 4    # four chips: the ShardGrid phase only

Batch phase: the paper's aggregated three-way chain over an R-MAT graph
with the ``amazon`` spec's skew, planned by ``plan_chain`` from exact
statistics and compiled whole by ``jit_execute_chain`` on a 4x4
``SimGrid`` with skew measurement on — once with the default join and
once with ``join_impl="fused"``.  The sum of path counts must equal the
exact 3-path count and no buffer may overflow.

Served phase: ``QueryEngine.submit`` of the triangle query on the same
kind of graph two scales smaller, four times (one cold build, three
cache hits); every answer must be ok and equal the host triangle
oracle.

Four-chip phase: the one-round ``1,3J`` Shares plan on a (2, 2) mesh and
the cascade on a (4,) mesh run through ``ShardGrid`` across four chips;
their output tuples must equal the ``SimGrid`` run of the same grid
shape and their counts the exact host count.

All data is generated in-process from ``--seed``.  The script refuses to
run (exit 2) where JAX finds no TPU; any failed check exits non-zero.
The last line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

KERNELS = ("hash_histogram", "segment_sum", "probe_counts")
GRID = (4, 4)
# The served triangle program's buffers are sized by the raw closing-hop
# join (caps_slack 8, rounded up to a power of two): 16.7M rows per
# device at scale 13.  Its TPU compile time grows with that size (about
# 220 s at scale 11 and 420 s at scale 12 on a 8-core host), so it runs
# two scales below the batch graph.
SERVED_SCALE_DROP = 2
# Compiling the ShardGrid programs for the chip takes about 45 s each at
# scale 8 and 75-100 s at scale 10 (and past 25 min at scale 12) on an
# 8-core host; the phase proves the mesh and its collectives, not size.
SHARD_SCALE = 8


def _import_repro() -> None:
    try:
        import repro  # noqa: F401 — installed, or on PYTHONPATH
    except ImportError:  # checkout: src/ next to this file
        sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
        import repro  # noqa: F401


def _log(msg: str) -> None:
    print(msg, flush=True)


def make_graph(scale: int, seed: int):
    """R-MAT edges with the ``amazon`` spec's skew and edge factor at
    ``2**scale`` nodes."""
    from repro.data.graphs import DATASETS, rmat_edges
    return rmat_edges(dataclasses.replace(DATASETS["amazon"], scale=scale),
                      seed=seed)


def tpu_kernels(hlo_text: str) -> dict:
    """Which of the engine's Pallas kernels the compiled program calls
    as a ``tpu_custom_call``."""
    calls = [ln for ln in hlo_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    return {k: any(k in ln for ln in calls) for k in KERNELS}


def peak_bytes() -> int | None:
    import jax
    st = jax.devices()[0].memory_stats()
    return None if st is None else st.get("peak_bytes_in_use")


def batch_phase(src, dst, stats, *, join_impl: str = "sort_merge") -> dict:
    """Aggregated three-way chain: plan, compile whole, run, check
    Σp against the exact 3-path count.  Returns what was measured."""
    import jax
    import numpy as np
    from repro.core import (ChainQuery, SimGrid, chain_edge_inputs,
                            default_chain_caps, jit_execute_chain, plan_chain)

    query = ChainQuery.three_way(aggregate=True)
    plan = plan_chain(stats, k=GRID[0] * GRID[1], aggregate=True)
    grid_shape = plan.grid_shape if plan.strategy == "one_round" else GRID
    caps = default_chain_caps(stats, grid_shape)
    rels = chain_edge_inputs(query, [(src, dst)] * 3, grid_shape)
    run = jit_execute_chain(SimGrid(grid_shape), query,
                            strategy=plan.strategy, caps=caps, donate=False,
                            measure_skew=True, join_impl=join_impl)

    t0 = time.perf_counter()
    compiled = run.lower(rels).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()   # the TPU compiler refuses misfits
    need = None if mem is None else (mem.argument_size_in_bytes
                                     + mem.output_size_in_bytes
                                     + mem.temp_size_in_bytes
                                     - mem.alias_size_in_bytes)

    t0 = time.perf_counter()
    out, _, ovf = compiled(rels)
    jax.block_until_ready(out)
    run_s = time.perf_counter() - t0

    valid = np.asarray(out.valid)
    paths = float(np.asarray(out.cols["p"], np.float64)[valid].sum())
    want = float(stats.prefix_joins[-1])
    overflow = bool(ovf)
    if overflow:
        raise AssertionError(f"batch {join_impl}: a buffer overflowed")
    if paths != want:
        raise AssertionError(f"batch {join_impl}: sum p = {paths}, "
                             f"exact 3-paths = {want}")
    return {"join_impl": join_impl, "strategy": plan.strategy,
            "grid": grid_shape, "rows_per_buffer": caps.join,
            "compile_s": compile_s, "run_s": run_s, "program_bytes": need,
            "paths": paths, "overflow": overflow,
            "kernels": tpu_kernels(compiled.as_text()),
            "peak_bytes_in_use": peak_bytes()}


def served_phase(src, dst, *, submits: int = 4) -> dict:
    """Triangle query through ``QueryEngine.submit``: one cold build,
    then cache hits; every answer must equal the host oracle."""
    from repro.core import JoinQuery, oracle_triangles, query_stats_exact
    from repro.serving import QueryEngine, QueryServeConfig, weighted_total

    query = JoinQuery.triangle()
    tables = [(src, dst)] * 3
    t0 = time.perf_counter()
    stats = query_stats_exact(query, tables)
    want = oracle_triangles(src, dst)
    host_s = time.perf_counter() - t0

    eng = QueryEngine(QueryServeConfig(k=8))
    latencies, hits = [], 0
    for i in range(submits):
        res = eng.submit(query, tables, stats=stats)
        if not res.ok or res.error is not None:
            raise AssertionError(f"submit {i}: {res.error_kind}: {res.error}")
        got = weighted_total(query, res.output) / 3
        if got != want:
            raise AssertionError(f"submit {i}: {got} triangles, "
                                 f"oracle {want}")
        if res.cache_hit != (i > 0):
            raise AssertionError(f"submit {i}: cache_hit={res.cache_hit}")
        hits += res.cache_hit
        latencies.append(res.latency_ms)
    return {"triangles": want, "submits": submits, "cache_hits": hits,
            "host_reference_s": host_s, "latency_ms": latencies,
            "strategy": res.plan.strategy, "grid": res.plan.grid_shape,
            "peak_bytes_in_use": peak_bytes()}


def _rows(rel, names) -> list:
    """Valid rows of a (*grid, cap) relation, device by device, as a
    (devices, sorted rows) list of int64 arrays."""
    import numpy as np
    valid = np.asarray(rel.valid).reshape(-1, rel.valid.shape[-1])
    cols = [np.asarray(rel.cols[n]).reshape(valid.shape) for n in names]
    out = []
    for d in range(valid.shape[0]):
        t = np.stack([c[d][valid[d]] for c in cols], axis=1).astype(np.int64)
        out.append(t[np.lexsort(t.T[::-1])] if len(t) else t)
    return out


def shard_phase(devices, *, scale: int = SHARD_SCALE, seed: int = 0) -> dict:
    """ShardGrid across ``devices`` (four) against SimGrid on the same
    grid shape: one-round 1,3J on (2, 2), cascade on (4,)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import (ChainQuery, ShardGrid, SimGrid, chain_edge_inputs,
                            chain_stats_exact, default_chain_caps,
                            execute_chain, jit_execute_chain)

    if len({d.id for d in devices}) != 4:
        raise AssertionError(f"need 4 distinct devices, got {devices}")
    src, dst = make_graph(scale, seed)
    stats = chain_stats_exact([(src, dst)] * 3)
    want = float(stats.prefix_joins[-1])
    query = ChainQuery.three_way(aggregate=False)
    names = ("a", "b", "c", "d")
    report = {"scale": scale, "edges": len(src), "paths": want}
    for strategy, shape in (("one_round", (2, 2)), ("cascade", (4,))):
        caps = default_chain_caps(stats, shape)
        rels = chain_edge_inputs(query, [(src, dst)] * 3, shape)
        t0 = time.perf_counter()
        sim, sim_st, sim_ovf = jit_execute_chain(
            SimGrid(shape), query, strategy=strategy, caps=caps,
            donate=False)(rels)
        jax.block_until_ready(sim)
        sim_s = time.perf_counter() - t0

        axes = ("x", "y")[:len(shape)]
        mesh = Mesh(np.array(devices).reshape(shape), axes)
        lead = len(shape)

        def body(grid_, *shards):
            flat = [jax.tree.map(lambda a: a.reshape(a.shape[lead:]), r)
                    for r in shards]
            out, st, ovf = execute_chain(grid_, query, flat,
                                         strategy=strategy, caps=caps)
            out = jax.tree.map(lambda a: a.reshape((1,) * lead + a.shape),
                               out)
            return out, st["read"], st["shuffled"], grid_.reduce_any(ovf)

        spec = P(*axes, None)
        t0 = time.perf_counter()
        shard, read, shuffled, ovf = jax.jit(
            lambda *r: ShardGrid(mesh, axes).run(
                body, *r, in_specs=tuple(spec for _ in r),
                out_specs=(spec, P(), P(), P())))(*rels)
        jax.block_until_ready(shard)
        shard_s = time.perf_counter() - t0

        placed = {d.id for d in shard.valid.sharding.device_set}
        if len(placed) != 4:
            raise AssertionError(f"{strategy}: output on devices {placed}")
        if bool(ovf) or bool(sim_ovf):
            raise AssertionError(f"{strategy}: a buffer overflowed")
        got = float(jnp.sum(shard.valid))
        if got != want or float(jnp.sum(sim.valid)) != want:
            raise AssertionError(f"{strategy}: {got} tuples, exact {want}")
        for a, b in zip(_rows(shard, names), _rows(sim, names)):
            if not np.array_equal(a, b):
                raise AssertionError(f"{strategy}: ShardGrid tuples differ "
                                     f"from SimGrid")
        if (float(read), float(shuffled)) != (float(sim_st["read"]),
                                              float(sim_st["shuffled"])):
            raise AssertionError(f"{strategy}: accounting differs")
        report[strategy] = {"grid": shape, "tuples": got,
                            "devices": sorted(placed),
                            "sim_s": sim_s, "shard_s": shard_s,
                            "shuffled": float(shuffled)}
    report["peak_bytes_in_use"] = peak_bytes()
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=13,
                    help="log2 nodes of the batch graph (13: the largest "
                         "whose program fits a 16 GB chip)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _import_repro()
    from repro.config import enable_compilation_cache
    cache_dir = enable_compilation_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    _log(f"device {dev.device_kind} x{len(devices)}, cache {cache_dir}")

    if args.chips == 4:
        if len(devices) < 4:
            print(f"chip_smoke: --chips 4 needs 4 devices, found "
                  f"{len(devices)}", file=sys.stderr)
            return 2
        _log(f"shard: {json.dumps(shard_phase(devices[:4], seed=args.seed))}")
    else:
        from repro.core import chain_stats_exact
        t0 = time.perf_counter()
        src, dst = make_graph(args.scale, args.seed)
        stats = chain_stats_exact([(src, dst)] * 3)
        _log(f"graph: scale {args.scale}, {len(src)} edges, "
             f"{stats.prefix_joins[-1]:.0f} 3-paths, grid {GRID}, "
             f"host stats {time.perf_counter() - t0:.1f}s")
        used = {k: False for k in KERNELS}
        for impl in ("sort_merge", "fused"):
            r = batch_phase(src, dst, stats, join_impl=impl)
            _log(f"batch: {json.dumps(r)}")
            used = {k: used[k] or r["kernels"][k] for k in KERNELS}
        served = args.scale - SERVED_SCALE_DROP
        src, dst = make_graph(served, args.seed)
        _log(f"served graph: scale {served}, {len(src)} edges")
        _log(f"served: {json.dumps(served_phase(src, dst))}")
        if not all(used.values()):
            raise AssertionError(f"Pallas kernels missing from the programs "
                                 f"that ran: {used}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
