"""§Roofline of the overlapped execution path: fused kernel speedup,
shuffle/compute overlap on an emulated 16-device mesh, and exact
bytes/FLOP accounting.

Three sections, emitted as ``BENCH_roofline.json`` and pinned by
``tests/test_bench_accounting.py``:

* ``fused_vs_staged`` — the per-reducer data plane at each capacity:
  the staged ``sort_merge_join`` (stable 3-operand ``lax.sort`` per
  side) vs the rank-packed ``fused_sort_merge_join``
  (``join_impl="fused"``), with the sort/probe phases timed separately
  so the win is attributable.  Gate (full mode): fused ≥ 1.5× at the
  16k capacity.

* ``overlap`` — one shuffle-heavy cascade hop on a real 16-device
  ShardGrid (emulated CPU devices via
  ``repro.config.configure_platform(host_devices=16)``, applied before
  JAX initializes): the barrier schedule (every chunk join depends on
  every chunk shuffle — MapReduce's sort/shuffle barrier) vs the
  production overlapped schedule (``overlap_chunks=C`` — chunk b's
  join depends only on chunk b's shuffle), with the hop's
  communication wall-clock isolated by differencing shuffle-only and
  local-only programs.  Gate (full mode): the overlap envelope
  evaluated on the measured component wall-clocks hides ≥ 0.3 of the
  communication; the directly-measured fraction is additionally gated
  when the host has more cores than emulated devices (see
  ``bench_overlap``).

* ``accounting`` — the same hop replayed on the deterministic SimGrid
  mirror: measured read/shuffled tuple counts, output matches, and the
  bytes-moved conversion (``relation_row_bytes``) each equal their
  analytic values exactly, in both modes.  The paper's communication
  accounting survives the overlapped schedule bit-for-bit.

Usage::

  PYTHONPATH=src python benchmarks/roofline.py [--fast] [--check]
                                               [--out BENCH_roofline.json]

``--fast`` shrinks capacities/repeats for CI smoke (wall-clock gates
are skipped: only the exact accounting is asserted); ``--check``
asserts the gates for the mode.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

try:
    import repro  # noqa: F401 — installed, or on PYTHONPATH (ROADMAP: PYTHONPATH=src)
except ImportError:  # checkout fallback: src/ relative to this file, not the cwd
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

OVERLAP_DEVICES = 16
OVERLAP_CHUNKS = 4
CAPACITIES = (1024, 4096, 16384)
FAST_CAPACITIES = (1024, 4096)


def _block_all(out) -> None:
    import jax
    for leaf in jax.tree_util.tree_leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def _timeit(fn, *args, repeats: int = 5) -> dict:
    import numpy as np
    _block_all(fn(*args))  # compile + warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        _block_all(fn(*args))
        times.append(time.perf_counter() - t0)
    return {"median_us": float(np.median(times) * 1e6),
            "min_us": float(np.min(times) * 1e6)}


# ---------------------------------------------------------------------------
# Section 1: fused vs staged per-reducer pipeline, per-phase
# ---------------------------------------------------------------------------

def bench_fused_vs_staged(capacities, repeats: int, rng) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import Relation
    from repro.core.local import (_sorted_by_key, fused_sort_merge_join,
                                  sort_merge_join)
    from repro.kernels import fused_join as fj

    report = {}
    for cap in capacities:
        left = Relation.from_arrays(
            cap,
            b=jnp.array(rng.integers(0, cap, cap), jnp.int32),
            v=jnp.array(rng.normal(size=cap), jnp.float32))
        right = Relation.from_arrays(
            cap,
            b=jnp.array(rng.integers(0, cap, cap), jnp.int32),
            w=jnp.array(rng.normal(size=cap), jnp.float32))
        out_cap = 4 * cap

        staged = jax.jit(lambda l, r, _c=out_cap: sort_merge_join(
            l, r, "b", "b", _c))
        fused = jax.jit(lambda l, r, _c=out_cap: fused_sort_merge_join(
            l, r, "b", "b", _c))

        # Phase timings: the (validity, key) sort each way, and the
        # probe (searchsorted run bounds) on the sorted columns.
        key, valid = left.col("b"), left.valid
        sort_staged = jax.jit(lambda k, v: _sorted_by_key(k, v))
        sort_fused = jax.jit(fj.stable_key_order)
        sorted_keys = jnp.sort(key)
        probe = jax.jit(lambda q, s: fj.probe_counts(q, s, backend="ref"))

        row = {
            "out_capacity": out_cap,
            "staged": _timeit(staged, left, right, repeats=repeats),
            "fused": _timeit(fused, left, right, repeats=repeats),
            "phases": {
                "sort_staged": _timeit(sort_staged, key, valid,
                                       repeats=repeats),
                "sort_fused": _timeit(sort_fused, key, valid,
                                      repeats=repeats),
                "probe": _timeit(probe, sorted_keys, sorted_keys,
                                 repeats=repeats),
            },
        }
        row["speedup_median"] = (row["staged"]["median_us"]
                                 / row["fused"]["median_us"])
        report[str(cap)] = row
        print(f"fused_vs_staged cap={cap:6d}: staged "
              f"{row['staged']['median_us']:10.1f} us  fused "
              f"{row['fused']['median_us']:10.1f} us  speedup "
              f"{row['speedup_median']:5.2f}x  (sort "
              f"{row['phases']['sort_staged']['median_us']:.0f} -> "
              f"{row['phases']['sort_fused']['median_us']:.0f} us)")
    return report


# ---------------------------------------------------------------------------
# Section 2: shuffle/compute overlap on the emulated 16-device mesh
# ---------------------------------------------------------------------------

def _overlap_inputs(rng, n_per_dev: int, cap: int, devices: int):
    """One shuffle-heavy hop's inputs, scattered over the 1-D mesh:
    several payload columns make the all-to-all carry real bytes."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import Relation

    def rel(key_name, payload_prefix):
        n = n_per_dev * devices
        cols = {key_name: jnp.array(rng.integers(0, n, n), jnp.int32)}
        for i in range(4):
            cols[f"{payload_prefix}{i}"] = jnp.array(
                rng.normal(size=n), jnp.float32)
        valid = np.zeros((devices, cap), bool)
        valid[:, :n_per_dev] = True
        out_cols = {}
        for name, c in cols.items():
            buf = np.zeros((devices, cap), np.asarray(c).dtype)
            buf[:, :n_per_dev] = np.asarray(c).reshape(devices, n_per_dev)
            out_cols[name] = jnp.asarray(buf)
        return Relation(out_cols, jnp.asarray(valid))

    return rel("b", "u"), rel("b", "w")


def bench_overlap(repeats: int, rng, *, devices: int, chunks: int,
                  n_per_dev: int = 8192) -> dict:
    """Wall-clock of one shuffle-heavy hop on a real ShardGrid, four
    jitted shard_map programs over identical inputs:

    * ``unchunked`` — the production staged hop (``overlap_chunks=1``).
    * ``barrier`` — the *same chunked op set* as the overlapped
      schedule, with an explicit data dependency from every per-chunk
      join back to ALL chunk shuffles: MapReduce's sort/shuffle barrier
      expressed over the chunk decomposition.  Identical work to
      ``overlapped``, so the pair isolates pure scheduling.
    * ``overlapped`` — the production ``overlap_chunks=C`` path: chunk
      b's join depends only on chunk b's shuffle.
    * ``shuffle_only`` — the full shuffle programs alone (both sides,
      no join), and ``local_only`` — the same minus the collective
      (map-side partition + flatten + compact, no ``all_to_all``).
      Their difference is the hop's *communication* wall-clock: in the
      paper's cost units the map-side partition is mapper CPU work,
      and the shuffle proper is the transfer.

    Two hidden fractions are reported:

    * ``model_hidden_fraction`` — the overlap envelope
      (:func:`~repro.core.cost_model.hop_time_overlapped`) evaluated
      on the *measured* component wall-clocks: what a scheduler that
      runs independent chains concurrently hides of the measured
      communication.  This is the roofline number — it is what the
      gate asserts (≥ 0.3), because it is a property of the schedule
      and the measured workload, not of the host's core count.
    * ``measured_hidden_fraction`` — ``(t_barrier − t_overlapped) /
      t_collective`` directly.  Only meaningful when the host has more
      cores than emulated devices (a 1-core CI container serializes
      all 16 devices, so *no* schedule can hide wall-clock there);
      gated only in that case, reported always, with ``host_cores``
      recorded alongside."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import Relation, ShardGrid, two_way_join
    from repro.core.cost_model import (hop_time_overlapped, hop_time_staged,
                                       overlap_hidden_fraction)
    from repro.core.local import local_join, partition
    from repro.core.relation import flatten_leading
    from repro.core.shuffle import compact_to, concat_rows, split_rows
    from repro.core.two_way import flat_grid_bucket, shuffle_to_device
    from repro.distributed.mesh import emulated_host_mesh

    cap = 2 * n_per_dev
    # ~4x slack over the expected n_per_dev/devices rows per
    # (device, source) slot: the send buffers stay O(rows), so the
    # shuffle cost is communication, not buffer zeroing.
    recv = max(512, (4 * n_per_dev) // devices)
    out_cap = 4 * n_per_dev
    mesh = emulated_host_mesh((devices,), ("d",))
    grid = ShardGrid(mesh, ("d",))
    left, right = _overlap_inputs(rng, n_per_dev, cap, devices)

    specs = dict(in_specs=(P("d", None), P("d", None)),
                 out_specs=(P("d"), P()))

    def _flat(r):
        # shard_map hands each device a (1, cap) block; the join layer
        # works on flat per-device relations.
        return jax.tree.map(lambda a: a.reshape(a.shape[1:]), r)

    def launch(c):
        def body(g, l, r):
            out, st, ovf = two_way_join(
                g, _flat(l), _flat(r), "b", "b", recv_capacity=recv,
                out_capacity=out_cap, local_capacity=cap,
                overlap_chunks=c)
            return out.count()[None], st["shuffled"][None]
        return jax.jit(lambda l, r: grid.run(body, l, r, **specs))

    def launch_barrier():
        # The overlapped chunk decomposition with the staged dependency
        # structure: shuffle every chunk, then join every chunk, each
        # join tied to all shuffles.
        def body(g, l, r):
            left_s = shuffle_to_device(g, _flat(l), "b", recv, 0, cap)[0]
            shuffled = [
                shuffle_to_device(g, chunk, "b", recv, 0, cap)[0]
                for chunk in split_rows(_flat(r), chunks)]
            tie = sum(c.col("b")[0] * 0 for c in shuffled)
            parts = []
            for chunk_s in shuffled:
                tied = Relation(
                    {**chunk_s.cols, "b": chunk_s.col("b") + tie},
                    chunk_s.valid)
                out_c, _ = local_join(left_s, tied, "b", "b", out_cap)
                parts.append(out_c)
            joined, _ = compact_to(g, concat_rows(parts), out_cap)
            n = g.reduce_sum(joined.count())
            return joined.count()[None], n.astype(jnp.float32)[None]
        return jax.jit(lambda l, r: grid.run(body, l, r, **specs))

    def shuffle_only():
        def body(g, l, r):
            ls = shuffle_to_device(g, _flat(l), "b", recv, 0, cap)[0]
            rs = shuffle_to_device(g, _flat(r), "b", recv, 0, cap)[0]
            return ls.count()[None], rs.count()[None]
        return jax.jit(lambda l, r: grid.run(
            body, l, r, in_specs=specs["in_specs"],
            out_specs=(P("d"), P("d"))))

    def local_only():
        # shuffle_only minus the all_to_all: identical map-side
        # partition + flatten + compaction.  shuffle_only − local_only
        # = the communication wall-clock.
        def body(g, l, r):
            outs = []
            for rel in (_flat(l), _flat(r)):
                b = flat_grid_bucket(g, rel.col("b"), salt=0)[0]
                buf, _ = partition(rel, b, devices, recv)
                outs.append(flatten_leading(buf).compact(cap).count()[None])
            return outs[0], outs[1]
        return jax.jit(lambda l, r: grid.run(
            body, l, r, in_specs=specs["in_specs"],
            out_specs=(P("d"), P("d"))))

    t_unchunked = _timeit(launch(1), left, right, repeats=repeats)
    t_barrier = _timeit(launch_barrier(), left, right, repeats=repeats)
    t_over = _timeit(launch(chunks), left, right, repeats=repeats)
    t_shuf = _timeit(shuffle_only(), left, right, repeats=repeats)
    t_local = _timeit(local_only(), left, right, repeats=repeats)

    unchunked_ms = t_unchunked["median_us"] / 1e3
    barrier_ms = t_barrier["median_us"] / 1e3
    over_ms = t_over["median_us"] / 1e3
    shuf_ms = t_shuf["median_us"] / 1e3
    # min-of-repeats for the subtraction: the two programs share their
    # map-side work, so min − min is the stablest transfer estimate.
    collective_ms = max(
        (t_shuf["min_us"] - t_local["min_us"]) / 1e3, 0.0)
    compute_ms = max(barrier_ms - collective_ms, 0.0)
    model_staged = hop_time_staged(collective_ms, compute_ms)
    model_over = hop_time_overlapped(collective_ms, compute_ms, chunks)
    report = {
        "devices": devices,
        "chunks": chunks,
        "rows_per_device": n_per_dev,
        "recv_capacity": recv,
        "host_cores": int(os.cpu_count() or 1),
        "unchunked_staged_ms": unchunked_ms,
        "barrier_ms": barrier_ms,
        "overlapped_ms": over_ms,
        "shuffle_only_ms": shuf_ms,
        "local_only_ms": t_local["median_us"] / 1e3,
        "collective_ms": collective_ms,
        "measured_hidden_fraction": overlap_hidden_fraction(
            barrier_ms, over_ms, collective_ms),
        "model_hidden_fraction": overlap_hidden_fraction(
            model_staged, model_over, collective_ms),
        "model": {"staged_ms": model_staged, "overlapped_ms": model_over},
    }
    print(f"overlap {devices}dev x{chunks}: unchunked {unchunked_ms:7.1f} ms"
          f"  barrier {barrier_ms:7.1f} ms  overlapped {over_ms:7.1f} ms"
          f"  collective {collective_ms:6.1f} ms  hidden model "
          f"{report['model_hidden_fraction']:5.2f} / measured "
          f"{report['measured_hidden_fraction']:5.2f} "
          f"({report['host_cores']} host cores)")
    return report


# ---------------------------------------------------------------------------
# Section 3: bytes / FLOP accounting, measured == analytic, both schedules
# ---------------------------------------------------------------------------

def bench_accounting(rng, *, devices: int, chunks: int,
                     n_per_dev: int = 512) -> dict:
    """The overlap hop on the SimGrid mirror: every measured count must
    equal its analytic value exactly, with the overlapped schedule
    measuring the *same* numbers as the staged one."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import SimGrid, two_way_join
    from repro.core.cost_model import estimate_join_size, relation_row_bytes

    cap = 2 * n_per_dev
    grid = SimGrid((devices,))
    left, right = _overlap_inputs(rng, n_per_dev, cap, devices)
    n_left = int(jnp.sum(left.valid))
    n_right = int(jnp.sum(right.valid))
    out_cap = 8 * n_per_dev

    rows = {}
    for label, c in (("staged", 1), ("overlapped", chunks)):
        out, st, ovf = two_way_join(
            grid, left, right, "b", "b", recv_capacity=cap,
            out_capacity=out_cap, local_capacity=cap, overlap_chunks=c)
        rows[label] = {
            "read": float(st["read"]),
            "shuffled": float(st["shuffled"]),
            "matches": int(jnp.sum(out.valid)),
            "overflow": bool(ovf),
        }

    lk = np.asarray(left.col("b"))[np.asarray(left.valid)]
    rk = np.asarray(right.col("b"))[np.asarray(right.valid)]
    row_bytes_l = relation_row_bytes(left)
    row_bytes_r = relation_row_bytes(right)
    analytic = {
        # Every input tuple is read once and shipped to its reducer
        # once (1 KVP per tuple on a two-way hop).
        "read": float(n_left + n_right),
        "shuffled": float(n_left + n_right),
        # The probe/expand FLOP unit: one emit per matching pair.
        "matches": int(estimate_join_size(lk, rk)),
        "shuffled_bytes": float(n_left * row_bytes_l
                                + n_right * row_bytes_r),
    }
    for label in rows:
        rows[label]["shuffled_bytes"] = (
            rows[label]["shuffled"] / analytic["shuffled"]
            * analytic["shuffled_bytes"]
            if analytic["shuffled"] else 0.0)
    report = {
        "devices": devices,
        "chunks": chunks,
        "row_bytes": {"left": row_bytes_l, "right": row_bytes_r},
        "measured": rows,
        "analytic": analytic,
    }
    print(f"accounting: read {rows['staged']['read']:.0f} "
          f"shuffled {rows['staged']['shuffled']:.0f} "
          f"matches {rows['staged']['matches']} "
          f"(analytic {analytic['matches']}) — overlapped identical: "
          f"{rows['staged'] == rows['overlapped']}")
    return report


# ---------------------------------------------------------------------------
# Gates
# ---------------------------------------------------------------------------

def check_report(report: dict) -> None:
    acc = report["accounting"]
    ana = acc["analytic"]
    for label, row in acc["measured"].items():
        assert row["read"] == ana["read"], (label, "read")
        assert row["shuffled"] == ana["shuffled"], (label, "shuffled")
        assert row["matches"] == ana["matches"], (label, "matches")
        assert row["shuffled_bytes"] == ana["shuffled_bytes"], (
            label, "bytes")
        assert not row["overflow"], label
    assert acc["measured"]["staged"] == acc["measured"]["overlapped"], (
        "overlapped schedule measured different tuple accounting")
    print("check OK: measured == analytic accounting, both schedules")

    if report["mode"] != "full":
        print("check (fast mode): wall-clock gates skipped")
        return
    top = str(max(int(c) for c in report["fused_vs_staged"]))
    sp = report["fused_vs_staged"][top]["speedup_median"]
    assert sp >= 1.5, (
        f"fused pipeline only {sp:.2f}x over staged at cap={top} "
        f"(gate: >= 1.5x)")
    ov = report["overlap"]
    hidden = ov["model_hidden_fraction"]
    assert hidden >= 0.3, (
        f"overlap envelope hides only {hidden:.2f} of the measured "
        f"communication wall-clock (gate: >= 0.3)")
    if ov["host_cores"] > ov["devices"]:
        measured = ov["measured_hidden_fraction"]
        assert measured >= 0.3, (
            f"measured overlap hides only {measured:.2f} of the "
            f"communication wall-clock on a {ov['host_cores']}-core host "
            f"(gate: >= 0.3)")
    else:
        print(f"check: measured hidden fraction "
              f"{ov['measured_hidden_fraction']:.2f} not gated "
              f"({ov['host_cores']} host cores serialize "
              f"{ov['devices']} emulated devices)")
    print(f"check OK: fused {sp:.2f}x >= 1.5x at {top}; "
          f"overlap envelope hides {hidden:.2f} >= 0.3")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke mode: small caps, 1 repeat, "
                         "wall-clock gates skipped")
    ap.add_argument("--check", action="store_true",
                    help="assert the roofline gates")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=OVERLAP_DEVICES)
    ap.add_argument("--out", default="BENCH_roofline.json")
    args = ap.parse_args()

    # Before any jax computation: the emulated mesh and (on GPU hosts)
    # the async-collective flags.
    from repro.config import configure_platform
    configure_platform(host_devices=args.devices)

    import jax
    import numpy as np

    caps = FAST_CAPACITIES if args.fast else CAPACITIES
    repeats = args.repeats if args.repeats else (1 if args.fast else 5)
    rng = np.random.default_rng(args.seed)

    report = {
        "benchmark": "roofline",
        "backend": jax.default_backend(),
        "mode": "fast" if args.fast else "full",
        "repeats": repeats,
        "capacities": list(caps),
        "fused_vs_staged": bench_fused_vs_staged(caps, repeats, rng),
        "overlap": bench_overlap(
            repeats, rng, devices=args.devices, chunks=OVERLAP_CHUNKS,
            n_per_dev=2048 if args.fast else 8192),
        "accounting": bench_accounting(
            rng, devices=args.devices, chunks=OVERLAP_CHUNKS),
    }
    # Write before gating so the artifact uploads even on a failed gate.
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.out}")
    if args.check:
        check_report(report)


# ---------------------------------------------------------------------------
# run.py rows
# ---------------------------------------------------------------------------

def bench_rows():
    """CSV rows for benchmarks/run.py (single-process: the fused sweep
    only — the overlap section needs a fresh process to emulate
    devices)."""
    import numpy as np
    rng = np.random.default_rng(0)
    rows = []
    rep = bench_fused_vs_staged((4096,), 3, rng)
    r = rep["4096"]
    rows.append(("roofline/fused_vs_staged_4k", r["speedup_median"],
                 f"staged={r['staged']['median_us']:.0f}us;"
                 f"fused={r['fused']['median_us']:.0f}us"))
    return rows


if __name__ == "__main__":
    main()
